"""Reduction of a profiler trace to device time.

:func:`read_xplane` reads the ``.xplane.pb`` that ``jax.profiler`` writes
into a :class:`Trace`: per device, the events of its ``XLA Modules`` line
(one per program run; the traced run compiles without per-op trace
points, so no ``XLA Ops`` line is recorded); and the spans of the host thread
that ran the benchmark's loop.  All are on the trace's one clock.  The
rest are pure functions over that record, so a recorded trace (a JSON
fixture) and a live one reduce the same way.

* busy time is the union of a device's event intervals, so programs
  that overlap count once;
* idle gaps are the stretches of the window that no event covers, each
  named by the innermost host span around it.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Dict, Iterable, List, Sequence, Tuple

DEVICE_PREFIX = "/device:"
DEVICE_LINE = "XLA Modules"


@dataclasses.dataclass(frozen=True)
class Op:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Op]]
    host: List[Op]

    @staticmethod
    def from_json(obj: dict) -> "Trace":
        return Trace(
            devices={
                k: [Op(*row) for row in v] for k, v in obj["devices"].items()
            },
            host=[Op(*row) for row in obj["host"]],
        )

    def span(self, name: str) -> Tuple[float, float]:
        """First start and last end of the host spans called ``name``."""
        spans = [o for o in self.host if o.name == name]
        if not spans:
            raise ValueError(f"no host span {name!r} in the trace")
        return min(o.start_ns for o in spans), max(o.end_ns for o in spans)


def read_xplane(path: str, marks: Sequence[str]) -> Trace:
    """The device events of every ``/device:`` plane, and every event of
    the host lines that hold one of the spans named in ``marks``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, List[Op]] = {}
    host: List[Op] = []
    for plane in data.planes:
        is_device = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            if is_device and line.name != DEVICE_LINE:
                continue
            events = [Op(e.name, e.start_ns, e.duration_ns) for e in line.events]
            if is_device:
                devices.setdefault(plane.name, []).extend(events)
            elif any(e.name in marks for e in events):
                host.extend(events)
    for ops in devices.values():
        ops.sort(key=lambda o: o.start_ns)
    return Trace(devices={k: v for k, v in devices.items() if v}, host=host)


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(ops: Iterable[Op], lo: float, hi: float) -> List[Tuple[float, float]]:
    """The ops' intervals cut to the window ``[lo, hi]``."""
    return [
        (max(o.start_ns, lo), min(o.end_ns, hi))
        for o in ops
        if o.end_ns > lo and o.start_ns < hi
    ]


def covered_ns(ops: Iterable[Op], lo: float, hi: float) -> float:
    """Length of the window ``[lo, hi]`` that some op covers."""
    return sum(e - s for s, e in union(clip(ops, lo, hi)))


def gaps(ops: Iterable[Op], lo: float, hi: float) -> List[Tuple[float, float]]:
    """The stretches of ``[lo, hi]`` that no op covers, in time order."""
    out, at = [], lo
    for s, e in union(clip(ops, lo, hi)):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def top_ops(trace: Trace, lo: float, hi: float, k: int = 10) -> List[list]:
    """The ``k`` op names with most device time in the window, in
    seconds per device."""
    total: Dict[str, float] = {}
    for ops in trace.devices.values():
        for o in ops:
            for s, e in clip([o], lo, hi):
                total[o.name] = total.get(o.name, 0.0) + (e - s)
    n = max(1, len(trace.devices))
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / n / 1e9] for name, ns in ranked]


def top_gaps(trace: Trace, lo: float, hi: float, k: int = 10) -> List[list]:
    """The ``k`` longest idle gaps on any device in the window, each named
    by the innermost host span that covers its midpoint (``"none"``
    where no host span does), in seconds."""
    found = []
    for ops in trace.devices.values():
        for s, e in gaps(ops, lo, hi):
            mid = (s + e) / 2
            cover = [h for h in trace.host if h.start_ns <= mid < h.end_ns]
            name = min(cover, key=lambda h: h.dur_ns).name if cover else "none"
            found.append([name, (e - s) / 1e9])
    return sorted(found, key=lambda g: -g[1])[:k]


def load(path: str) -> Trace:
    """A trace recorded as JSON: ``{"devices": {plane: [[name, start_ns,
    dur_ns], ...]}, "host": [[name, start_ns, dur_ns], ...]}``."""
    with open(path) as f:
        return Trace.from_json(json.load(f))
