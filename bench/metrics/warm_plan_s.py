"""Host planning per warm count: the program's ``tc.plan`` spans in the
traced window (plan-cache lookup with the graph's digest, staging) over
the counts made in it."""
from bench import spans


def read(run):
    return spans.per_count_s(run, (spans.PLAN,))
