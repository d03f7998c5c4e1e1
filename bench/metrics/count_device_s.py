"""Device busy time per count: the union of a device's op intervals in
the traced window over the counts made in it, averaged over devices."""
from bench import trace


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace_window
    busy = [trace.covered_ns(ops, lo, hi) for ops in run.trace.devices.values()]
    return sum(busy) / len(busy) / 1e9 / len(run.count_times)
