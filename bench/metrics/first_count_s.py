"""Host clock around the first count of the freshly generated graph:
plan, stage, compile or load from the compile cache, count."""


def read(run):
    return run.first_count_s
