"""The program's own spans in a traced window.

The program wraps each count in host spans (``tc.count``, ``tc.plan``,
``tc.dispatch``, ``tc.wait``, ``tc.fetch``, ...) on the profiler's clock,
on the thread that calls it, which is the thread that holds the harness's
``bench.window``.  These helpers read them from a :class:`bench.trace.Trace`;
a program without such spans reads ``None``, so a metric built on them
leaves the line rather than reading zero.
"""
from __future__ import annotations

from typing import Iterable, List, Optional, Sequence, Tuple

from bench import trace

PLAN = "tc.plan"
DISPATCH = "tc.dispatch"
WAIT = "tc.wait"
FETCH = "tc.fetch"


Intervals = List[Tuple[float, float]]


def intervals(run, names: Sequence[str]) -> Optional[Intervals]:
    """The union of the host spans called one of ``names``, cut to the
    traced window; ``None`` without a trace or without such a span."""
    if run.trace is None:
        return None
    lo, hi = run.trace_window
    found = trace.union(
        trace.clip((o for o in run.trace.host if o.name in names), lo, hi)
    )
    return found or None


def per_count_s(run, names: Sequence[str]) -> Optional[float]:
    """Seconds inside the spans ``names`` in the window, per count."""
    found = intervals(run, names)
    if found is None:
        return None
    return sum(e - s for s, e in found) / 1e9 / len(run.count_times)


def overlap_ns(a: Iterable[Tuple[float, float]],
               b: Iterable[Tuple[float, float]]) -> float:
    """Length of the intersection of two sets of disjoint sorted
    intervals."""
    a, b = list(a), list(b)
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_under_pct(run, names: Sequence[str]) -> Optional[float]:
    """Share of the window in which a device is idle while the host is
    inside one of the spans ``names``, averaged over devices."""
    inside = intervals(run, names)
    if inside is None:
        return None
    lo, hi = run.trace_window
    shares = [
        overlap_ns(trace.gaps(ops, lo, hi), inside) / (hi - lo)
        for ops in run.trace.devices.values()
    ]
    return 100.0 * sum(shares) / len(shares)
