"""Share, in percent, of the window's plans that reached the preemption cycle
(preempted or rejected) for which the guaranteed shortlist could not prove its
winner and dispatched the full sweep (``DeviceClusterState.fallbacks``)."""


def read(run):
    reached = sum(d[1] != "placed" for d in run.decisions)
    if not reached:
        return None
    return 100.0 * run.fallbacks.get("shortlist_full_sweep", 0) / reached
