"""Window time over the counts made in it: the warm time per count,
every stall in the window included."""


def read(run):
    return run.window_s / len(run.count_times)
