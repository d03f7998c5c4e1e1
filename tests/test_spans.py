"""The program's spans, read back from a profiler trace on the CPU.

One traced session counts a small graph cold and warm, as a batch, as a
delta, and once with a fault armed at ``device_stage``; the tests read the
host spans back with the benchmark's own trace reader (``bench.trace``) and,
for the ``count_id`` and ``memo`` the spans carry, with
``jax.profiler.ProfileData``.
"""
from __future__ import annotations

import sys
import warnings
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import trace as bench_trace  # noqa: E402

MISS_ONLY = ("tc.plan.relabel", "tc.plan.pack", "tc.stage")
LAUNCH = ("tc.dispatch", "tc.wait", "tc.fetch")


class Call:
    """The spans of one ``tc.count`` and its ``count_id``."""

    def __init__(self, top, spans, count_id):
        self.top = top
        self.spans = spans  # time order, tc.count excluded
        self.count_id = count_id

    def named(self, name):
        return [s for s in self.spans if s.name == name]

    def one(self, name):
        (s,) = self.named(name)
        return s


def _inside(inner, outer) -> bool:
    return outer.start_ns <= inner.start_ns and inner.end_ns <= outer.end_ns


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    import jax

    from repro.core import count_triangles, count_triangles_delta, rmat
    from repro.core import count_triangles_many
    from repro.pipeline import EdgeDelta, PlanCache
    from repro.runtime.faultinject import FaultPlan, StageFault

    g = rmat(7, 8, seed=3)
    cache = PlanCache()
    out = tmp_path_factory.mktemp("trace")
    results = {}
    with jax.profiler.trace(str(out)):
        results["cold"] = count_triangles(g, q=1, cache=cache)
        results["warm"] = count_triangles(g, q=1, cache=cache)
        results["many"] = count_triangles_many(
            [g, rmat(6, 8, seed=4)], q=1, cache=cache
        )
        results["delta"] = count_triangles_delta(
            g, EdgeDelta.random_flips(g, 3, seed=5),
            artifact=results["warm"].artifact, cache=cache,
        )
        with pytest.raises(StageFault):
            count_triangles(
                g, q=1, cache=cache,
                fault_plan=FaultPlan.parse("device_stage=stagefault"),
            )
    (path,) = out.glob("plugins/profile/*/*.xplane.pb")
    host = bench_trace.read_xplane(str(path), ("tc.count",)).host
    host = sorted(
        (o for o in host if o.name.startswith("tc.")),
        key=lambda o: (o.start_ns, -o.dur_ns),
    )
    stats = _event_stats(str(path))
    ids = {k: v.get("count_id") for k, v in stats.items()}
    tops = [o for o in host if o.name == "tc.count"]
    calls = {}
    for label, top in zip(("cold", "warm", "many", "delta", "fault"), tops):
        inside = [o for o in host if o is not top and _inside(o, top)]
        calls[label] = Call(top, inside, ids[(top.name, top.start_ns)])
    return calls, results, ids, host, stats


def _event_stats(path):
    """The metadata of every ``tc.*`` host event, by (name, start)."""
    from jax.profiler import ProfileData

    out = {}
    with warnings.catch_warnings():
        # the stats' pybind type warns that it has no __module__
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("tc."):
                        out[(e.name, e.start_ns)] = dict(e.stats)
    return out


def test_every_call_is_one_count_span(traced):
    calls, _, _, host, _ = traced
    assert sum(o.name == "tc.count" for o in host) == 5
    # no program span lies outside a count
    tops = [c.top for c in calls.values()]
    assert all(any(_inside(o, t) for t in tops) for o in host)


def test_cold_count_nests_the_plan_stages(traced):
    calls, _, _, _, _ = traced
    cold = calls["cold"]
    plan = cold.one("tc.plan")
    assert _inside(plan, cold.top)
    for name in ("tc.plan.digest",) + MISS_ONLY:
        assert _inside(cold.one(name), plan), name


def test_warm_count_hashes_and_plans_nothing(traced):
    calls, _, _, _, _ = traced
    warm = calls["warm"]
    assert _inside(warm.one("tc.plan.digest"), warm.one("tc.plan"))
    for name in MISS_ONLY:
        assert not warm.named(name), name


@pytest.mark.parametrize("label,memo", [("cold", 0), ("warm", 1)])
def test_digest_span_says_whether_the_kept_digest_served(traced, label, memo):
    calls, _, _, _, stats = traced
    digest = calls[label].one("tc.plan.digest")
    assert stats[(digest.name, digest.start_ns)].get("memo") == memo


@pytest.mark.parametrize("label", ["cold", "warm"])
def test_dispatch_wait_fetch_follow_the_plan(traced, label):
    call = traced[0][label]
    seq = [call.one("tc.plan")] + [call.one(n) for n in LAUNCH]
    for before, after in zip(seq, seq[1:]):
        assert before.end_ns <= after.start_ns, (before.name, after.name)


def test_one_count_id_per_call(traced):
    calls, _, ids, host, _ = traced
    for call in calls.values():
        got = {ids[(s.name, s.start_ns)] for s in call.spans}
        assert got == {call.count_id}
    assert len({c.count_id for c in calls.values()}) == len(calls)
    assert None not in {c.count_id for c in calls.values()}


@pytest.mark.parametrize("label", ["cold", "warm", "delta"])
def test_preprocess_seconds_is_the_plan_span(traced, label):
    calls, results, _, _, _ = traced
    plan = calls[label].named("tc.plan")[-1]  # the count's own
    assert results[label].preprocess_seconds == pytest.approx(
        plan.dur_ns / 1e9, abs=1e-3
    )
    launch = sum(calls[label].one(n).dur_ns for n in LAUNCH) / 1e9
    assert results[label].count_seconds == pytest.approx(launch, abs=1e-3)


def test_batch_carries_the_spans(traced):
    calls, results, _, _, _ = traced
    many = calls["many"]
    plan = many.one("tc.plan")
    for name in ("tc.plan.digest",) + MISS_ONLY:
        assert _inside(many.one(name), plan), name
    seq = [plan] + [many.one(n) for n in LAUNCH]
    for before, after in zip(seq, seq[1:]):
        assert before.end_ns <= after.start_ns
    assert results["many"].plan_seconds == pytest.approx(
        plan.dur_ns / 1e9, abs=1e-3
    )


def test_delta_carries_the_spans(traced):
    calls, results, _, _, _ = traced
    delta = calls["delta"]
    splice, count = delta.named("tc.plan")
    assert _inside(delta.one("tc.plan.delta"), splice)
    assert splice.end_ns <= count.start_ns
    seq = [count] + [delta.one(n) for n in LAUNCH]
    for before, after in zip(seq, seq[1:]):
        assert before.end_ns <= after.start_ns
    assert results["delta"].delta["level"] in ("splice", "repack", "rebase")


def test_a_fault_at_device_stage_still_closes_the_plan(traced):
    calls, _, _, _, _ = traced
    fault = calls["fault"]
    plan = fault.one("tc.plan")
    assert _inside(plan, fault.top) and plan.dur_ns > 0
    for name in LAUNCH:
        assert not fault.named(name), name


def test_span_records_its_time_through_an_exception():
    from repro.core import spans

    seconds = {}
    with pytest.raises(RuntimeError):
        with spans.span("tc.plan", seconds, "plan"):
            raise RuntimeError("planning failed")
    assert seconds["plan"] >= 0
    count_id = spans._COUNT_ID.get
    assert count_id() is None
    with spans.count_scope():
        outer = count_id()
        with spans.count_scope():  # a nested entry keeps the request's id
            assert count_id() == outer
    assert count_id() is None
