"""95th percentile, by nearest rank, of the window's count times."""
from bench.harness import nearest_rank


def read(run):
    return nearest_rank(run.count_times, 95)
