"""Median time of ``plan()`` over the window's decisions that the normal
cycle placed, in milliseconds."""
import statistics


def read(run):
    times = [d[2] * 1e3 for d in run.decisions if d[1] == "placed"]
    return statistics.median(times) if times else None
