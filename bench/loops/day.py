"""The ``day`` loop: whole repeats of one co-location day through
``ColocationSim.run``, each from the same start.

The day is the mix's own, drawn from its ``day_seed``, and the same for every
run: the program draws a day's arrivals from its seed, and days drawn from the
run's seed differ in how many plans they make, which changed the work from
seed to seed.  The run's seed is not used.

The mix gives the `ColocationConfig` (offline arrivals per node-hour, job
length, initial jobs per node, requeue, backfill chunk, horizon) and the online
classes on the diurnal curve (peak replicas per 100 nodes, trough share).  One
whole day runs in set-up, which warms every program the day uses; the window
repeats the day until ``--seconds`` have passed, at least once.  Every plan of
the first repeat is checked against the plain reference, and every repeat
(and the warm-up day) must make the same plans.
"""
from __future__ import annotations

import time
import types

from bench import check, fleet
from bench.measure import Window, annotator, device_info, quiet
from bench.program import Recorder, program_spec, scheduler_kw

#: the host spans the trace reduction attributes idle device time to
SPANS = ("run",)


def day_config(cfg: dict, mix: dict, seed: int, engine_overrides=None):
    from repro.core import ColocationConfig

    spec, _ = program_spec(cfg)
    n = cfg["num_nodes"]
    return ColocationConfig(
        num_nodes=n, spec=spec, seed=seed,
        horizon_hours=mix["horizon_hours"], tick_hours=mix["tick_hours"],
        requeue_delay_hours=mix["requeue_delay_hours"],
        backfill_chunk=mix["backfill_chunk"],
        offline_rate_per_hour=mix["offline_per_node_hour"] * n,
        mean_job_hours=mix["mean_job_hours"],
        initial_offline_jobs=mix["initial_jobs_per_node"] * n,
        requeue=mix["requeue"], **scheduler_kw(cfg, engine_overrides))


def day_policies(cfg: dict, mix: dict):
    """Online classes on the diurnal curve: peak replicas per 100 nodes
    scaled to the fleet, trough a fixed share of the peak."""
    from repro.core.autoscale import AutoscalePolicy

    _, wls = program_spec(cfg)
    scale = cfg["num_nodes"] / 100.0
    out = []
    for name, p in mix["online"].items():
        peak = max(p["min_peak"], round(p["peak_per_100_nodes"] * scale))
        out.append(AutoscalePolicy(wls[name],
                                   max(1, round(peak * p["trough_share"])),
                                   peak))
    return out


class Day:
    """One repeat of the day: a fresh `ColocationSim`."""

    def __init__(self, cfg: dict, mix: dict, seed: int,
                 engine_overrides=None) -> None:
        from repro.core import ColocationSim

        self.sim = ColocationSim(day_config(cfg, mix, seed, engine_overrides),
                                 policies=day_policies(cfg, mix))
        self.rec = Recorder()
        self.sim.cluster.add_inst_listener(self.rec.on_inst)
        self.sim.sched.add_listener(self._on_decision)
        sched, rec = self.sim.sched, self.rec
        plan = sched.plan

        def recorded_plan(workload, *, view=None, allow_normal=True,
                          allow_preempt=True, **kw):
            same = view is not None and view is rec.last_view
            txn = plan(workload, view=view, allow_normal=allow_normal,
                       allow_preempt=allow_preempt, **kw)
            rec.last_view = txn.view
            rec.plan(same, workload, allow_normal, allow_preempt, txn)
            return txn

        sched.plan = recorded_plan

    def _on_decision(self, dec, event: str) -> None:
        if event == "committed":
            self.rec.commit(dec)

    def run(self, annotate=quiet) -> None:
        with annotate("run"):
            self.sim.run()


def run(cfg, mix, seed, seconds, traced, engine_overrides, watch, t_start):
    """Set-up, the window and the check; returns ``(run, got, attempted,
    device)`` for `bench.run.run_cell`."""
    seed = mix["day_seed"]
    warm = Day(cfg, mix, seed, engine_overrides)
    warm.run()
    warm_plans = [r for r in warm.rec.log if r[0] == "plan"]
    del warm
    setup_s = time.perf_counter() - t_start
    annotate = annotator(traced)
    mark = watch.mark()
    days = []
    with Window(traced, SPANS) as win:
        while not days or time.perf_counter() - win.t0 < seconds:
            day = Day(cfg, mix, seed, engine_overrides)
            day.run(annotate)
            days.append(day)
    compiles = watch.delta(mark)
    device = device_info(cfg["chips"])
    reports = [d.sim.report for d in days]
    events = sum(d.sim.events_processed for d in days)
    logs = [d.rec.log for d in days]
    del days
    plans = [i for i, r in enumerate(logs[0]) if r[0] == "plan"]
    got = check.replay(logs[0], fleet.topology(cfg), fleet.requests(cfg),
                       cfg["alpha"], cfg["num_nodes"], [], set(plans))
    first = [logs[0][i] for i in plans]
    got["repeats_differing"] = sum(
        [r for r in log if r[0] == "plan"] != first
        for log in logs[1:] + [warm_plans])
    got["summary"] = (f"window: {len(logs)} repeats of the day, {events} "
                      f"events, {len(plans)} plans a day; {compiles} programs built in "
                      f"the window")
    out = types.SimpleNamespace(
        setup_s=setup_s, window_s=win.seconds, compiles=compiles,
        trace=win.trace, reports=reports, events=events)
    return out, got, len(plans) * len(logs), device
