"""Engine equivalence: every (schedule × operand-store × kernel)
combination must produce identical counts on the same graphs.

q=1 combinations run in-process; q in {2, 3} run in subprocesses with
XLA host devices via the ``distributed_runner`` fixture (conftest.py).
"""
import pytest

from repro.core import (
    available_schedules,
    count_triangles,
    get_schedule,
    named_graph,
    rmat,
    triangle_count_oracle,
)

# (schedule, method) -> operand store exercised (see DESIGN.md §2):
#   cannon/search|search2|global -> CSRStore (blob)
#   cannon/dense                 -> DenseStore
#   cannon/tile                  -> TileStore (bit-packed 128x128)
#   summa/search                 -> SummaCSRStore (panel broadcast)
#   oned/search                  -> OneDCSRStore (ring blob)
COMBOS = [
    ("cannon", "search"),
    ("cannon", "search2"),
    ("cannon", "global"),
    ("cannon", "dense"),
    ("cannon", "tile"),
    ("summa", "search"),
    ("oned", "search"),
]

GRAPHS = ["bull", "karate", "rmat"]


def _graph(name):
    if name == "rmat":
        return rmat(9, 8, seed=42)
    return named_graph(name)


@pytest.mark.parametrize("name", ["cannon", "summa", "oned"])
def test_registry_contains_bundled_schedules(name):
    assert name in available_schedules()
    spec = get_schedule(name)
    assert callable(spec.runner)
    assert callable(spec.build_fn)


def test_registry_refuses_unknown_schedule():
    with pytest.raises(ValueError, match="unknown schedule"):
        get_schedule("nope")


def test_csr_kernel_registry():
    from repro.core.engine import CSR_KERNELS, make_csr_kernel

    assert {"search", "search2", "global"} <= set(CSR_KERNELS)
    with pytest.raises(ValueError, match="unknown CSR count method"):
        make_csr_kernel("nope", dpad=1, chunk=1)
    with pytest.raises(ValueError, match="bucketized plan"):
        make_csr_kernel("search2", dpad=1, chunk=1)


@pytest.mark.parametrize("graph_name", GRAPHS)
@pytest.mark.parametrize("schedule,method", COMBOS)
def test_equivalence_q1(graph_name, schedule, method):
    g = _graph(graph_name)
    exp = triangle_count_oracle(g)
    r = count_triangles(g, q=1, schedule=schedule, method=method)
    assert r.triangles == exp, (graph_name, schedule, method)


def test_per_device_counts_sum_to_global():
    """Reduction(global_sum=False) partials must psum to the same total."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import build_plan, preprocess
    from repro.core.api import make_grid_mesh
    from repro.core.cannon import build_cannon_fn

    g = _graph("rmat")
    exp = triangle_count_oracle(g)
    g2, _ = preprocess(g)
    plan = build_plan(g2, 1)
    fn = build_cannon_fn(plan, make_grid_mesh(1), reduce_global=False)
    per = fn(**{k: jnp.asarray(v) for k, v in plan.device_arrays().items()})
    assert int(np.asarray(per).sum()) == exp


DIST_CODE = """
import jax
jax.config.update("jax_enable_x64", True)
from repro.core import count_triangles, named_graph, rmat, triangle_count_oracle

COMBOS = {combos}
for gname in {graphs!r}:
    g = rmat(9, 8, seed=42) if gname == "rmat" else named_graph(gname)
    exp = triangle_count_oracle(g)
    for schedule, method in COMBOS:
        r = count_triangles(g, q={q}, schedule=schedule, method=method)
        assert r.triangles == exp, (gname, schedule, method, r.triangles, exp)
        print(f"{{gname}}/{{schedule}}/{{method}}: {{r.triangles}} ok")
print("ALL-OK")
"""


@pytest.mark.parametrize("q", [2, 3])
def test_equivalence_distributed(q, distributed_runner):
    out = distributed_runner(
        DIST_CODE.format(combos=COMBOS, graphs=GRAPHS, q=q),
        ndev=q * q,
        timeout=1200,
    )
    assert "ALL-OK" in out


def test_custom_schedule_registration():
    """A new schedule is one registration away (and unregisterable by
    overwrite) — the extension point future PRs plug into."""
    from repro.core.api import RunContext, register_schedule

    calls = {}

    def runner(graph, mesh, ctx: RunContext):
        calls["ctx"] = ctx
        return 7, None

    register_schedule("seven", runner)
    try:
        g = _graph("bull")
        r = count_triangles(g, q=1, schedule="seven")
        assert r.triangles == 7
        assert calls["ctx"].q == 1
    finally:
        from repro.core.api import _SCHEDULES

        _SCHEDULES.pop("seven", None)


def test_legacy_runner_receives_relabeled_graph():
    """Runners registered without ``plans_itself`` keep the pre-pipeline
    contract: ``count_triangles`` relabels before dispatch, so
    ``reorder=True`` still applies the paper's §5.3 degree ordering."""
    import numpy as np

    from repro.core.api import register_schedule
    from repro.pipeline import relabel_stage

    seen = {}

    def runner(graph, mesh, ctx):
        seen["graph"] = graph
        # the relabel options were consumed before dispatch
        assert ctx.reorder is False and ctx.cyclic_p is None
        return 0, None

    register_schedule("legacy", runner)
    try:
        g = _graph("karate")
        expected, _ = relabel_stage(g, reorder=True, cyclic_p=None)

        count_triangles(g, q=1, schedule="legacy", reorder=True)
        np.testing.assert_array_equal(seen["graph"].edges, expected.edges)

        count_triangles(g, q=1, schedule="legacy", reorder=False)
        np.testing.assert_array_equal(seen["graph"].edges, g.edges)
    finally:
        from repro.core.api import _SCHEDULES

        _SCHEDULES.pop("legacy", None)
