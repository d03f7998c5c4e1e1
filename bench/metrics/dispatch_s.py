"""Engine dispatch per warm count: the program's ``tc.dispatch`` spans
in the traced window (the call that enqueues the device program) over
the counts made in it."""
from bench import spans


def read(run):
    return spans.per_count_s(run, (spans.DISPATCH,))
