"""Median over every admission in the window of the time from ``plan()``
being called to ``commit()`` returning, in milliseconds."""
import statistics


def read(run):
    if not run.decisions:
        return None
    return statistics.median((d[2] + d[3]) * 1e3 for d in run.decisions)
