"""Median time of ``plan()`` over the window's decisions that preempted, in
milliseconds: the shortlist prescreen, the exact sweep and placement."""
import statistics


def read(run):
    times = [d[2] * 1e3 for d in run.decisions if d[1] == "preempted"]
    return statistics.median(times) if times else None
