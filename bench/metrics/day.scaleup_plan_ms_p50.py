"""Median over the window's repeats of ``ColocationReport.scaleup_plan_p50_us``:
the per-request plan time of the autoscaler's scale-up ``plan_batch`` calls
alone, backfill and requeue plans left out, in milliseconds.  Nothing where the
program reports no scale-up."""
import statistics


def read(run):
    vals = [r.scaleup_plan_p50_us for r in run.reports
            if getattr(r, "scaleup_calls", 0)]
    return statistics.median(vals) / 1e3 if vals else None
