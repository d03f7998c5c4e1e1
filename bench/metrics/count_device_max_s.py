"""The slowest device's busy time per count: the union of each device's
op intervals in the traced window over the counts made in it, the most
of any device.  On a mesh the slowest chip sets the pace of every
count, which ``count_device_s`` averages away; on one chip the two are
the same."""
from bench import trace


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace_window
    busy = [trace.covered_ns(ops, lo, hi) for ops in run.trace.devices.values()]
    return max(busy) / 1e9 / len(run.count_times)
