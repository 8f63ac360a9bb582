"""Median over the window's repeats of ``ColocationReport.plan_p50_us``: the
day's per-request plan time, batched plans amortised over their chunk, in
milliseconds."""
import statistics


def read(run):
    return statistics.median(r.plan_p50_us for r in run.reports) / 1e3
