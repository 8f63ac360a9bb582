"""Median time of ``Transaction.commit()`` over the window's admissions, in
milliseconds: the host journal and cluster bookkeeping."""
import statistics


def read(run):
    if not run.decisions:
        return None
    return statistics.median(d[3] * 1e3 for d in run.decisions)
