"""Seconds from the start of the process to the start of the window: start
state, scheduler, compilation or cache loads, and the warm-up traffic."""


def read(run):
    return run.setup_s
