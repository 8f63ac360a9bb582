"""The program-span reduction: per-plan stage times, device idle time given to
the innermost covering program span, device time per program."""
import pytest

from bench import spans


def _planes(host, device_ops, modules=()):
    return [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": host}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": list(modules)},
            {"name": "XLA Ops", "events": device_ops}]},
    ]


# window 0..1000; one plan 100..600 holding sync 100..200, a dispatch
# 250..300 and a readback 300..500; a commit 600..700; a day tick 700..1000
# around a second plan 800..900 whose sync 800..850
HOST = [("window", 0, 1000),
        ("sched.plan", 100, 500), ("sched.sync", 100, 100),
        ("sched.dispatch", 250, 50), ("sched.readback", 300, 200),
        ("sched.commit", 600, 100),
        ("day.tick", 700, 300), ("sched.plan", 800, 100),
        ("sched.sync", 800, 50),
        ("plan", 100, 500)]        # the benchmark's own span: not read here


def test_a_gap_under_nested_spans_goes_to_the_innermost():
    # device busy 0..50 and 450..460 only
    got = spans.reduce(_planes(HOST, [("fusion", 0, 50), ("copy", 450, 10)]))
    idle = dict(got["idle_by_program_span"])
    assert idle == pytest.approx({
        "outside program spans": 50e-9,
        "sched.sync": 150e-9,          # 100..200 and 800..850
        "sched.plan": 200e-9,          # 200..250, 500..600, 850..900
        "sched.dispatch": 50e-9,
        "sched.readback": 190e-9,      # 300..500 less the busy 450..460
        "sched.commit": 100e-9,
        "day.tick": 200e-9,            # 700..800 and 900..1000
    })
    assert sum(idle.values()) == pytest.approx(1000e-9 - 60e-9)


def test_self_time_is_a_span_less_its_children():
    got = spans.reduce(_planes(HOST, [("fusion", 0, 1)]))
    assert got["plans"] == 2
    first, second = got["per_plan"]
    assert first == pytest.approx({"sched.sync": 100e-9,
                                   "sched.dispatch": 50e-9,
                                   "sched.readback": 200e-9})
    assert second == pytest.approx({"sched.sync": 50e-9})
    idle = dict(got["idle_by_program_span"])
    # the plans' own time: 500 - 350 and 100 - 50
    assert idle["sched.plan"] == pytest.approx(200e-9)
    # outermost sched.plan / sched.commit spans over the window
    assert got["sched_share"] == pytest.approx(0.7)


def test_spans_cut_by_the_window_are_clipped():
    host = [("window", 100, 400),                               # 100..500
            ("sched.plan", 0, 200), ("sched.sync", 50, 100),    # ..200, ..150
            ("sched.commit", 300, 300)]                         # 300..600
    got = spans.reduce(_planes(host, [("fusion", 0, 1000)],
                               modules=[("jit_plan2(77)", 450, 200)]))
    assert got["plans"] == 0          # the plan began before the window
    assert got["sched_share"] == pytest.approx(300 / 400)
    assert dict(got["device_by_program"]) == pytest.approx(
        {"jit_plan2": 50e-9})
    got = spans.reduce(_planes(host, [("fusion", 490, 100)]))
    assert dict(got["idle_by_program_span"]) == pytest.approx({
        "sched.sync": 50e-9, "sched.plan": 50e-9,
        "outside program spans": 100e-9, "sched.commit": 190e-9})


def test_module_times_sum_per_name():
    modules = [("jit_plan2(1)", 0, 10), ("jit_plan2(2)", 20, 30),
               ("jit_delta_encode(3)", 60, 5), ("jit_plan2", 70, 1)]
    got = spans.reduce(_planes([("window", 0, 100)], [("fusion", 0, 1)],
                               modules))
    assert got["device_by_program"] == [
        ["jit_plan2", pytest.approx(41e-9)],
        ["jit_delta_encode", pytest.approx(5e-9)]]


def test_per_layer_numbers_of_a_window():
    red = spans.reduce(_planes(HOST, [("fusion", 0, 1)]))
    got = spans.per_layer(red, {"dispatches": 3, "h2d_bytes": 1000}, "admit")
    assert got == pytest.approx({
        "dispatches_per_plan.admit": 1.5,
        "sync_ms_p50.admit": 75e-6, "patch_ms_p50.admit": 0.0,
        "route_ms_p50.admit": 0.0, "dispatch_ms_p50.admit": 25e-6,
        "readback_ms_p50.admit": 100e-6, "h2d_bytes_per_plan.admit": 500.0})
    assert spans.per_layer(red, {"dispatches": 4}, "day") == pytest.approx(
        {"dispatches_per_plan.day": 2.0, "sched_share.day": 70.0})
