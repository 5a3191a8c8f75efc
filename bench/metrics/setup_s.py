"""Seconds from process start to the window's start: imports, building
the system, data, warm-up and, in a run that compiles, compilation."""


def read(run):
    return run.setup_s
