"""Events the co-location day loop processed in the window (whole repeats of
one seeded day), over the window's length on the host clock."""


def read(run):
    return run.events / run.window_s
