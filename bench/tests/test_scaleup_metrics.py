"""The 1,024-node day cell on the CPU, cut to a small fleet, and the readers
of the day's scale-up metrics (``day.scaleup_plan_ms_p50``,
``day.batch_builds_per_scaleup``) on stub runs."""
import types

import pytest

from bench import run

#: a three-hour day, with the plans it must check
DAY_3H = {"horizon_hours": 3.0, "check_plans_min": 50}

#: the metrics of the day's scale-ups, read from the window's reports
SCALEUP_METRICS = ("day.scaleup_plan_ms_p50", "day.batch_builds_per_scaleup")


def test_thousand_node_day_cell_is_correct_and_reads_its_scale_ups(
        monkeypatch):
    """The 1,024-node day cell cut to 64 rows, still above its shortlist's
    K, and to the first six hours, which scale up through a batch session:
    every plan of a repeat checked, and the scale-up metrics read from the
    window the loop hands back."""
    loop = run.loop
    runs = []

    def keeping(name):
        mod = loop(name)

        def kept(*a):
            got = mod.run(*a)
            runs.append(got[0])
            return got

        return type("Loop", (), {"run": staticmethod(kept)})

    monkeypatch.setattr(run, "loop", keeping)
    out = run.run_cell("rtx4090.1024.day", 2**31 + 5, 0.5, False,
                       require_chip=False,
                       traffic_overrides=dict(DAY_3H, horizon_hours=6.0),
                       config_overrides={"num_nodes": 64, "shortlist_k": 16})
    assert out["correct"] is True
    assert set(out["metrics"]) == {"day_events_per_s", "setup_s"}
    checks = out["checks"]
    plans = int(out["summary"].split(" plans a day")[0].rsplit(" ", 1)[1])
    assert checks["plans_checked"]["value"] == plans >= 50
    assert checks["repeats_differing"]["value"] == 0
    reports = runs[0].reports
    assert all(r.scaleup_calls > 0 and r.batch_builds > 0 for r in reports)
    assert run.reader("day.scaleup_plan_ms_p50")(runs[0]) > 0
    assert 0 < run.reader("day.batch_builds_per_scaleup")(runs[0]) <= 1


def _day_run(*reports):
    return types.SimpleNamespace(
        reports=[types.SimpleNamespace(**r) for r in reports])


def test_scaleup_plan_ms_p50_is_the_median_over_repeats():
    got = run.reader("day.scaleup_plan_ms_p50")(_day_run(
        *({"scaleup_calls": 4, "scaleup_plan_p50_us": us}
          for us in (3000.0, 1000.0, 2500.0))))
    assert got == pytest.approx(2.5)


def test_batch_builds_per_scaleup_divides_the_window_totals():
    got = run.reader("day.batch_builds_per_scaleup")(_day_run(
        {"scaleup_calls": 8, "batch_builds": 3},
        {"scaleup_calls": 8, "batch_builds": 3}))
    assert got == pytest.approx(3 / 8)


@pytest.mark.parametrize("name", SCALEUP_METRICS)
def test_a_day_with_no_scale_up_reads_nothing(name):
    """No scale-up, or a program that does not report them, reads None and
    divides by nothing."""
    none = {"scaleup_calls": 0, "scaleup_plan_p50_us": 0.0,
            "batch_builds": 0}
    assert run.reader(name)(_day_run(none, none)) is None
    assert run.reader(name)(_day_run({"plan_p50_us": 2000.0})) is None
