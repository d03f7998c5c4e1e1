"""CPU tests of the readers of the program's spans (``bench/spans.py``,
``warm_plan_s``, ``dispatch_s``, ``result_idle_pct``).

They reduce a trace recorded on the chip by hand and check the readers
against it, and plant idle gaps under chosen spans of a synthetic trace.
"""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, trace  # noqa: E402

# three warm counts of kron-s16 on one v5e, traced as a --trace 1 run
# traces, with the program's spans
FIXTURE = ROOT / "bench" / "fixtures" / "trace_spans_kron-s16.json"
READERS = ("warm_plan_s", "dispatch_s", "result_idle_pct")


def _run(tr, window, counts):
    cell = harness.load_cell("kron-s16.warm", ROOT)
    return harness.Run(
        cell=cell, setup_s=1.0, backend_start_s=0.5, first_count_s=1.0,
        plan_s=0.5, count_times=[1.0] * counts, window_s=1.0,
        trace=tr, trace_window=window,
    )


def _read(name, run):
    return harness.reader(run.cell, name)(run)


@pytest.fixture(scope="module")
def recorded():
    tr = trace.load(str(FIXTURE))
    counts = sum(o.name == harness.COUNT_SPAN for o in tr.host)
    return _run(tr, tr.span(harness.WINDOW_SPAN), counts)


def test_recorded_trace_holds_the_programs_spans(recorded):
    names = [o.name for o in recorded.trace.host]
    assert len(recorded.count_times) == 3
    for name in ("tc.count", "tc.plan", "tc.plan.digest") + (
        "tc.dispatch", "tc.wait", "tc.fetch"
    ):
        assert names.count(name) == 3, name
    ops = [o.name for ops in recorded.trace.devices.values() for o in ops]
    assert ops and all(n.startswith("jit_tc_engine(") for n in ops)


def test_readers_give_the_hand_reduced_values(recorded):
    # the fixture's tc.plan and tc.dispatch durations, over 3 counts
    plan_ns = 14004129 + 13979539 + 13952749
    dispatch_ns = 398450 + 408620 + 347150
    assert _read("warm_plan_s", recorded) == pytest.approx(plan_ns / 3e9)
    assert _read("dispatch_s", recorded) == pytest.approx(dispatch_ns / 3e9)
    # the device's gaps after programs 1-3, while the host was still in
    # tc.wait (up to its end) and then in tc.fetch
    under = (
        (1741291622 - 1739036401) + 791700
        + (3439249132 - 3437039420) + 794109
        + (5136906245 - 5134828779) + 669510
    )
    window_ns = 5093557040
    assert _read("result_idle_pct", recorded) == pytest.approx(
        100 * under / window_ns
    )


def test_idle_is_planning_dispatch_and_the_result(recorded):
    """The idle share is the host's planning, its dispatch and the wait
    for the result, up to the trace's own clock offset: each program
    starts on the device's clock up to 1.08 ms before its dispatch starts
    on the host's, which moves that much idle from tc.plan to tc.wait."""
    lo, hi = recorded.trace_window
    count_s = (hi - lo) / 1e9 / len(recorded.count_times)
    idle = _read("device_idle_pct", recorded)
    plan = _read("warm_plan_s", recorded)
    dispatch = _read("dispatch_s", recorded)
    result = _read("result_idle_pct", recorded)
    parts = result + 100 * (plan + dispatch) / count_s
    assert abs(parts - idle) < 0.2
    programs = [o for ops in recorded.trace.devices.values() for o in ops]
    starts = [
        o.start_ns for o in recorded.trace.host if o.name == "tc.dispatch"
    ]
    offset_ns = max(d - p.start_ns for d, p in zip(starts, programs))
    assert 0 < offset_ns < 2e6
    assert parts - idle <= 100 * (offset_ns / 1e9 + dispatch) / count_s


def _synthetic(gap_under=None):
    """Two counts in a 200 ns window, each span at a fixed place, the
    device busy throughout; ``gap_under`` cuts a 4 ns hole in the first
    program under that span."""
    holes = {"tc.plan": (5, 9), "tc.wait": (50, 54), "tc.fetch": (92, 96)}
    host = [trace.Op(harness.WINDOW_SPAN, 0, 200)]
    for base in (0, 100):
        host += [
            trace.Op(harness.COUNT_SPAN, base, 100),
            trace.Op("tc.plan", base + 2, 10),
            trace.Op("tc.dispatch", base + 12, 2),
            trace.Op("tc.wait", base + 14, 76),
            trace.Op("tc.fetch", base + 90, 8),
        ]
    busy = [(0, 100), (100, 200)]
    if gap_under is not None:
        a, b = holes[gap_under]
        busy[0:1] = [(0, a), (b, 100)]
    ops = [trace.Op("jit_tc_engine(1)", s, e - s) for s, e in busy]
    tr = trace.Trace(devices={"/device:TPU:0": ops}, host=host)
    return _run(tr, (0, 200), 2)


def test_a_gap_under_the_fetch_raises_result_idle_by_its_length():
    assert _read("result_idle_pct", _synthetic()) == 0
    for span in ("tc.fetch", "tc.wait"):
        planted = _read("result_idle_pct", _synthetic(span))
        assert planted == pytest.approx(100 * 4 / 200), span


def test_a_gap_under_the_plan_leaves_result_idle():
    planted = _synthetic("tc.plan")
    assert _read("device_idle_pct", planted) == pytest.approx(100 * 4 / 200)
    assert _read("result_idle_pct", planted) == 0
    assert _read("warm_plan_s", planted) == pytest.approx(10 / 1e9)
    assert _read("dispatch_s", planted) == pytest.approx(2 / 1e9)


@pytest.mark.parametrize("name", READERS)
def test_readers_give_nothing_without_a_trace_or_the_spans(name):
    run = _synthetic()
    run.trace = run.trace_window = None
    assert _read(name, run) is None
    # a program without spans (the parent's) leaves the metric out
    bare = _synthetic()
    bare.trace.host = [
        o for o in bare.trace.host if not o.name.startswith("tc.")
    ]
    assert _read(name, bare) is None
