"""Share of the traced window in which no operation ran on a device,
averaged over the devices used."""
from bench import trace


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace_window
    idle = [
        1.0 - trace.covered_ns(ops, lo, hi) / (hi - lo)
        for ops in run.trace.devices.values()
    ]
    return 100.0 * sum(idle) / len(idle)
