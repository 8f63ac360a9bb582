"""Batch sessions built (``ColocationReport.batch_builds``, counted in
`persistent_batch_session`) per scale-up ``plan_batch`` call
(``scaleup_calls``) over the window's repeats: 1 would be a fresh vmapped class
dispatch for every chunk, reuses lower it.  Nothing where the program reports
no scale-up."""


def read(run):
    calls = sum(getattr(r, "scaleup_calls", 0) for r in run.reports)
    if not calls:
        return None
    return sum(r.batch_builds for r in run.reports) / calls
