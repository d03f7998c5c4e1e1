"""Set-up: process start to the first timed count (generation, the
first count with its plan and compile or cache load, the warm-up)."""


def read(run):
    return run.setup_s
