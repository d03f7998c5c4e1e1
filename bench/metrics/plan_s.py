"""Host planning of the first count: the program's own
``TCResult.preprocess_seconds`` (relabel, decompose, pack, stage)."""


def read(run):
    return run.plan_s
