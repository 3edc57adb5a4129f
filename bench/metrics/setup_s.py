"""Set-up time: process start to the first timed call (imports, graph
generation, the program's Graph build and placement, the warm-up solve
with its compile or cache load)."""


def read(run):
    return run.setup_s
