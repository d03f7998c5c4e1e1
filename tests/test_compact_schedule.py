"""Compacted kept-step schedules (DESIGN.md §4.4) + staged aug keys +
deterministic autotune (§5).

Covers: live-step derivation and the σ visit-order search, compacted vs
uncompacted count equivalence across (schedule × store × method) on the
empty-block fixtures, host-staged intersection-key parity with the
on-device build (x64 on and off), the compacted stepper's checkpoint
round trip, autotune determinism (plan-cache hits), and the two-level
kernel's ignored-kwarg warning.
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
from repro.core.plan import (
    CompactSchedule,
    compact_live_steps,
    host_aug_keys,
    resolve_compact_steps,
)
from repro.pipeline import plan_cannon, plan_oned, plan_summa
from repro.pipeline.cache import PlanCache
from repro.pipeline.stages import choose_cannon_skew


# ======================================================================
# live-step derivation + σ search
# ======================================================================
def test_compact_live_steps_and_hops():
    keep = np.zeros((2, 2, 5), dtype=bool)
    keep[0, 1, 1] = True
    keep[1, 0, 4] = True
    cs = compact_live_steps(keep)
    assert cs.n_total == 5
    assert cs.live_steps == (1, 4)
    assert cs.n_elided == 3
    # hops: prologue to step 1, then the fused 1 -> 4 jump
    assert cs.hops == (1, 3)

    empty = compact_live_steps(np.zeros((3, 3, 3), dtype=bool))
    assert empty.live_steps == () and empty.n_elided == 3


def test_choose_cannon_skew_concentrates_cliques():
    """Block-diagonal graph: the default alignment leaves every step
    live (device (x,x) lives at shift -x mod q); the σ search must find
    the visit order putting all live work on one step."""
    q = 3
    g, _ = preprocess(residue_cliques(q, 8))
    plan = build_plan(g, q)
    assert compact_live_steps(plan.step_keep).n_live == q  # default: all live
    sigma, n_live = choose_cannon_skew(plan.step_keep)
    assert n_live == 1
    assert sorted(sigma) == list(range(q))  # a true permutation

    # the re-packed σ plan's mask realizes exactly that live count, with
    # the same number of kept (device, step) pairs (σ only re-times them)
    splan = build_plan(g, q, skew_perm=sigma)
    assert compact_live_steps(splan.step_keep).n_live == 1
    assert int(splan.step_keep.sum()) == int(plan.step_keep.sum())


def test_choose_cannon_skew_identity_on_dense():
    g, _ = preprocess(rmat(8, 8, seed=3))
    plan = build_plan(g, 3)
    sigma, n_live = choose_cannon_skew(plan.step_keep)
    assert sigma == (0, 1, 2)  # nothing to gain: identity, byte-stable plans
    assert n_live == 3


def test_pipeline_attaches_sigma_and_compact():
    art = plan_cannon(residue_cliques(3, 12), 3, cache=PlanCache())
    plan = art.plan
    assert plan.skew_perm is not None and sorted(plan.skew_perm) == [0, 1, 2]
    assert plan.compact is not None and plan.compact.n_live == 1
    assert art.compact is plan.compact
    # summa/oned: no free visit order, but live lists are staged
    assert plan_summa(
        residue_cliques(3, 12), 3, 3, cache=PlanCache()
    ).compact is not None
    oned = plan_oned(residue_cliques(3, 12), 9, cache=PlanCache())
    assert oned.compact.live_steps == (0, 3, 6)  # rings hop in clique strides


def test_sigma_pack_matches_loop_reference():
    from repro.core.plan import _build_plan_loops
    from repro.pipeline.stages import pack_tc_plan

    g, _ = preprocess(residue_cliques(3, 8))
    sigma = (0, 2, 1)
    fast = pack_tc_plan(g, 3, skew_perm=sigma, aug_keys=True)
    ref = _build_plan_loops(g, 3, skew_perm=sigma, aug_keys=True)
    for name, arr in fast.device_arrays().items():
        assert arr.tobytes() == ref.device_arrays()[name].tobytes(), name


def test_resolve_compact_steps_contract():
    g, _ = preprocess(named_graph("karate"))
    plan = build_plan(g, 2)  # raw pack: no compaction stage ran
    assert resolve_compact_steps(plan, None) is None
    with pytest.raises(ValueError, match="no compacted schedule"):
        resolve_compact_steps(plan, True)
    plan.compact = CompactSchedule(n_total=2, live_steps=(0,))
    assert resolve_compact_steps(plan, None) == (0,)
    assert resolve_compact_steps(plan, False) is None
    # auto never compacts batched/multi-pod engines; explicit True errors
    assert resolve_compact_steps(plan, None, batched=True) is None
    assert resolve_compact_steps(plan, None, npods=2) is None
    with pytest.raises(ValueError, match="batched or multi-pod"):
        resolve_compact_steps(plan, True, batched=True)
    # nothing elided -> auto keeps the scan body
    plan.compact = CompactSchedule(n_total=2, live_steps=(0, 1))
    assert resolve_compact_steps(plan, None) is None


# ======================================================================
# compacted == uncompacted (q=1 in-process; q=3 subprocess below)
# ======================================================================
SPARSE_FIXTURES = {
    "cliques": lambda: residue_cliques(3, 8),
    "star": lambda: star(37),
    "edgeless": lambda: Graph.from_edges(6, [], [], name="empty"),
}

COMBOS = [
    ("cannon", "search"),
    ("cannon", "search2"),
    ("cannon", "global"),
    ("cannon", "dense"),
    ("cannon", "tile"),
    ("cannon", "auto"),
    ("summa", "search"),
    ("summa", "auto"),
    ("oned", "search"),
    ("oned", "auto"),
]


@pytest.mark.parametrize("graph_name", sorted(SPARSE_FIXTURES))
@pytest.mark.parametrize("schedule,method", COMBOS)
def test_compacted_equals_uncompacted_q1(graph_name, schedule, method):
    g = SPARSE_FIXTURES[graph_name]()
    exp = triangle_count_oracle(g)
    compacted = count_triangles(g, q=1, schedule=schedule, method=method)
    full = count_triangles(
        g, q=1, schedule=schedule, method=method, compact=False
    )
    assert compacted.triangles == full.triangles == exp


def test_superset_live_steps_are_valid():
    """Keeping a globally-dead step live is always correct — the
    contract the stepper's resume path relies on."""
    import jax.numpy as jnp

    from repro.core.api import make_grid_mesh
    from repro.core.cannon import build_cannon_fn

    g = residue_cliques(2, 8)
    exp = triangle_count_oracle(g)
    g2, _ = preprocess(g)
    plan = build_plan(g2, 1)
    plan.compact = CompactSchedule(n_total=1, live_steps=(0,))
    fn = build_cannon_fn(plan, make_grid_mesh(1), compact=True)
    arrays = {k: jnp.asarray(v) for k, v in plan.device_arrays().items()}
    assert int(fn(**arrays)) == exp


DIST_COMPACT_CODE = """
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np
from repro.core import (Graph, count_triangles, residue_cliques, star,
                        triangle_count_oracle)

q = 3
fixtures = [residue_cliques(q, 12), star(10 * q + 1),
            Graph.from_edges(6, [], [], name="empty")]
combos = {combos}
for g in fixtures:
    exp = triangle_count_oracle(g)
    for schedule, method in combos:
        m = count_triangles(g, q=q, schedule=schedule, method=method)
        u = count_triangles(g, q=q, schedule=schedule, method=method,
                            compact=False)
        n = count_triangles(g, q=q, schedule=schedule, method=method,
                            compact=False, use_step_mask=False)
        assert m.triangles == u.triangles == n.triangles == exp, (
            g.name, schedule, method, m.triangles, u.triangles,
            n.triangles, exp)
        cs = getattr(m.plan, "compact", None)
        assert cs is not None, (g.name, schedule)
        if g.name.startswith("cliques") and schedule == "cannon":
            assert cs.n_live == 1, (g.name, schedule, cs)
        if g.name == "empty":
            assert cs.n_live == 0, (g.name, schedule, cs)
        print(f"{{g.name}}/{{schedule}}/{{method}} ok")
print("ALL-OK")
"""


def test_compacted_equivalence_distributed(distributed_runner):
    combos = [
        ("cannon", "search"), ("cannon", "global"), ("cannon", "search2"),
        ("cannon", "dense"), ("cannon", "tile"), ("cannon", "auto"),
        ("summa", "search"), ("oned", "search"),
    ]
    out = distributed_runner(
        DIST_COMPACT_CODE.format(combos=combos), ndev=9, timeout=1800
    )
    assert "ALL-OK" in out


# ======================================================================
# host-staged aug keys: parity with the on-device build (x64 on & off)
# ======================================================================
def _assert_aug_parity(plan):
    import jax.numpy as jnp

    from repro.core.count import build_aug_keys

    q = plan.q
    for x in range(q):
        for y in range(q):
            dev = build_aug_keys(
                jnp.asarray(plan.b_indptr[x, y]),
                jnp.asarray(plan.b_indices[x, y]),
            )
            assert np.array_equal(np.asarray(dev), plan.b_aug[x, y]), (x, y)
    assert np.all(np.diff(plan.b_aug, axis=-1) >= 0)  # sorted per block


def test_staged_aug_keys_parity_x64_off():
    """Default test process runs with x64 off: int32 keys, staged and
    on-device builds must agree bit for bit and count identically."""
    from repro import compat

    assert not compat.x64_enabled()
    g, _ = preprocess(residue_cliques(3, 8))
    plan = build_plan(g, 3, aug_keys=True)
    assert plan.b_aug is not None and plan.b_aug.dtype == np.int32
    _assert_aug_parity(plan)

    exp = triangle_count_oracle(residue_cliques(3, 8))
    for method in ("global", "search2"):
        staged = count_triangles(
            residue_cliques(3, 8), q=1, method=method
        )
        assert staged.triangles == exp


DIST_AUG_CODE = """
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np
import jax.numpy as jnp
from repro.core import build_plan, preprocess, rmat, triangle_count_oracle
from repro.core.api import make_grid_mesh
from repro.core.cannon import build_cannon_fn
from repro.core.count import build_aug_keys

q = 2
g = rmat(8, 8, seed=21)
exp = triangle_count_oracle(g)
g2, _ = preprocess(g)
plan = build_plan(g2, q, aug_keys=True)
assert plan.b_aug is not None
for x in range(q):
    for y in range(q):
        dev = build_aug_keys(jnp.asarray(plan.b_indptr[x, y]),
                             jnp.asarray(plan.b_indices[x, y]))
        assert np.array_equal(np.asarray(dev), plan.b_aug[x, y]), (x, y)

mesh = make_grid_mesh(q)
arrays = {k: jnp.asarray(v) for k, v in plan.device_arrays().items()}
staged = build_cannon_fn(plan, mesh, method="global")
plain_plan = build_plan(g2, q, aug_keys=False)
plain = build_cannon_fn(plain_plan, mesh, method="global")
plain_arrays = {k: jnp.asarray(v)
                for k, v in plain_plan.device_arrays().items()}
a = int(staged(**arrays))
b = int(plain(**plain_arrays))
assert a == b == exp, (a, b, exp)
print("AUG-OK", a)
"""


def test_staged_aug_keys_distributed_x64_on(distributed_runner):
    out = distributed_runner(DIST_AUG_CODE, ndev=4, timeout=900)
    assert "AUG-OK" in out


def test_batched_global_uses_staged_keys():
    graphs = [residue_cliques(2, 6), star(13), named_graph("karate")]
    expected = [triangle_count_oracle(g) for g in graphs]
    res = count_triangles_many(graphs, q=1, method="global")
    assert res.triangles == expected


def test_host_aug_keys_refuses_unstageable_width(monkeypatch):
    """Past the int32 key range with x64 off the host build must return
    None (staging would silently truncate on device)."""
    from repro import compat

    assert not compat.x64_enabled()
    nb = 46341
    indptr = np.zeros((1, 1, nb + 1), dtype=np.int32)
    indices = np.zeros((1, 1, 1), dtype=np.int32)
    assert host_aug_keys(indptr, indices) is None


# ======================================================================
# compacted stepper: checkpoint round trip across an elided schedule
# ======================================================================
DIST_STEPPER_CODE = """
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np
import jax.numpy as jnp
from repro.core import residue_cliques, triangle_count_oracle
from repro.core.api import make_grid_mesh
from repro.core.cannon import build_cannon_fn, build_cannon_stepper
from repro.core.plan import CompactSchedule, compact_live_steps
from repro.pipeline import plan_cannon
from repro.pipeline.cache import PlanCache

q = 3
g = residue_cliques(q, 8)
exp = triangle_count_oracle(g)
art = plan_cannon(g, q, cache=PlanCache())
plan = art.plan
true_live = plan.compact.live_steps
assert plan.compact.n_live == 1, plan.compact
# widen to a 2-step live list (supersets of the true live set are valid
# schedules) so the checkpoint lands *between* live steps
extra = next(s for s in range(q) if s not in true_live)
live = tuple(sorted(set(true_live) | {extra}))
plan.compact = CompactSchedule(n_total=q, live_steps=live)

mesh = make_grid_mesh(q)
stepper = build_cannon_stepper(plan, mesh)
assert stepper.live_steps == live
assert stepper.n_carry == 4  # compacted stepper: single payload generation
arrays = {k: jnp.asarray(v) for k, v in plan.device_arrays().items()}
statics = {k: arrays[k] for k in ("m_ti", "m_tj", "m_cnt", "step_keep")}

carry = list(stepper.prime(arrays))
acc = jnp.zeros((q, q), jnp.int64)
saved = None
for s in live:
    if s == live[1]:  # checkpoint mid-loop, host numpy round trip
        saved = ([np.asarray(c).copy() for c in carry],
                 np.asarray(acc).copy(), s)
    out = stepper(tuple(carry) + (acc,), statics, step=s)
    carry, acc = list(out[:-1]), out[-1]
total = int(np.asarray(acc).sum())

carry2 = [jnp.asarray(c) for c in saved[0]]
acc2 = jnp.asarray(saved[1])
for s in [t for t in live if t >= saved[2]]:
    out = stepper(tuple(carry2) + (acc2,), statics, step=s)
    carry2, acc2 = list(out[:-1]), out[-1]
total2 = int(np.asarray(acc2).sum())
assert total == total2 == exp, (total, total2, exp)
for a, b in zip(carry, carry2):
    assert np.asarray(a).tobytes() == np.asarray(b).tobytes()

# and the compacted scan engine agrees
fn = build_cannon_fn(plan, mesh)
assert int(fn(**arrays)) == exp
print("COMPACT-STEPPER-OK")
"""


def test_compacted_stepper_checkpoint_roundtrip(distributed_runner):
    out = distributed_runner(DIST_STEPPER_CODE, ndev=9, timeout=1200)
    assert "COMPACT-STEPPER-OK" in out


# ======================================================================
# autotune: determinism + auto-method resolution
# ======================================================================
def test_autotune_deterministic_and_cached():
    g = rmat(8, 8, seed=5)
    cache = PlanCache()
    a1 = plan_cannon(g, 2, autotune=True, cache=cache)
    a2 = plan_cannon(g, 2, autotune=True, cache=cache)
    assert a2.cache_hit and a1.plan.chunk == a2.plan.chunk
    # same graph through a fresh cache: identical shapes (no timing, no
    # randomness anywhere in the stage)
    b = plan_cannon(g, 2, autotune=True, cache=PlanCache())
    assert not b.cache_hit
    assert b.plan.chunk == a1.plan.chunk
    assert b.autotune == a1.autotune
    assert b.plan.n_long == a1.plan.n_long
    assert b.plan.d_small == a1.plan.d_small
    # the autotune knob is a cache-key component
    c = plan_cannon(g, 2, autotune=False, cache=cache)
    assert not c.cache_hit and c.autotune is None


@pytest.mark.parametrize("schedule", ["cannon", "summa", "oned"])
def test_autotune_counts_stay_exact_after_reorder(schedule):
    g = rmat(8, 8, seed=5)
    exp = triangle_count_oracle(g)
    r = count_triangles(g, q=1, schedule=schedule, method="auto")
    assert r.triangles == exp, (schedule, r.triangles)


def test_auto_resolves_search2_with_staged_keys_on_heavy_tail():
    """A pendant-heavy hub clique keeps p90 probe length at 1 while the
    clique rows reach ~39: auto must resolve to search2 and re-plan with
    staged aug keys (the search resolution never pays for them)."""
    iu, ju = np.triu_indices(30, k=1)
    src = np.concatenate([iu, np.full(8000, 0)])
    dst = np.concatenate([ju, np.arange(30, 8030)])
    g = Graph.from_edges(8030, src, dst, name="hubclique")
    exp = triangle_count_oracle(g)
    r = count_triangles(g, q=1, method="auto")
    assert r.method == "search2"
    assert r.triangles == exp
    assert r.plan.autotune["tail_heavy"]
    assert r.plan.b_aug is not None  # re-planned with staged keys

    flat = count_triangles(g, q=1, method="auto")  # warm cache path
    assert flat.triangles == exp and flat.method == "search2"

    light = count_triangles(rmat(7, 8, seed=2), q=1, method="auto")
    assert light.method == "search"
    assert light.plan.b_aug is None  # search resolution stages no keys


def test_auto_method_resolution():
    from repro.core.api import _resolve_auto_method

    class P:
        pass

    p = P()
    assert _resolve_auto_method(p) == "search"  # no autotune report
    p.autotune = dict(tail_heavy=False)
    assert _resolve_auto_method(p) == "search"
    p.autotune = dict(tail_heavy=True)
    p.n_long = 7
    assert _resolve_auto_method(p) == "search2"
    q = P()
    q.autotune = dict(tail_heavy=True, n_long=None)  # oned: no split
    assert _resolve_auto_method(q) == "search"


def test_pick_chunk_properties():
    from repro.pipeline.stages import _pick_chunk

    assert _pick_chunk(100, 8) == 128  # pow2 cover of the task list
    assert _pick_chunk(100000, 8) == 4096  # hard cap
    assert _pick_chunk(100000, 100000) == 64  # budget-bound floor
    assert _pick_chunk(1, 1) == 64


# ======================================================================
# two-level kernel: ignored-kwarg warning (satellite guard rail)
# ======================================================================
def test_two_level_warns_once_on_ignored_kwargs(monkeypatch):
    import jax.numpy as jnp

    from repro.core import count as count_mod

    monkeypatch.setattr(count_mod, "_TWO_LEVEL_KW_WARNED", False)
    args = (
        jnp.asarray(np.array([0, 1], np.int32)),  # a_indptr (nb=1)
        jnp.asarray(np.array([0], np.int32)),
        jnp.asarray(np.array([0, 1], np.int32)),
        jnp.asarray(np.array([0], np.int32)),
        jnp.zeros(1, jnp.int32),
        jnp.zeros(1, jnp.int32),
        jnp.asarray(1),
        1,
    )
    kw = dict(dpad_long=1, dpad_short=1, chunk=1)
    with pytest.warns(UserWarning, match="ignores probe_shorter"):
        count_mod.count_pair_search_two_level(
            *args, probe_shorter=False, **kw
        )
    # one-time: a second offending call stays silent
    import warnings

    with warnings.catch_warnings():
        warnings.simplefilter("error")
        count_mod.count_pair_search_two_level(
            *args, probe_shorter=False, sentinel=7, **kw
        )

    # defaults (and the engine's search2 factory) never warn
    monkeypatch.setattr(count_mod, "_TWO_LEVEL_KW_WARNED", False)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        count_mod.count_pair_search_two_level(*args, **kw)
