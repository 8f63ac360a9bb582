"""99th percentile over every admission in the window of the time from
``plan()`` being called to ``commit()`` returning, in milliseconds (at least
100 admissions, so one lies beyond it)."""
import statistics


def read(run):
    if len(run.decisions) < 100:
        return None
    return statistics.quantiles([(d[2] + d[3]) * 1e3 for d in run.decisions],
                                n=100, method="inclusive")[98]
