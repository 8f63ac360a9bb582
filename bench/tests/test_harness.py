"""The harness end to end on the CPU at 128 nodes (the look for a chip
skipped): the result line, and the comparison with the plain reference
catching each planted fault."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import measure, run

ROOT = Path(__file__).resolve().parents[2]
SMALL = {"num_nodes": 128}


def _cell(engine_overrides=None, seconds=2.0, cell="rtx4090.10240.ramp"):
    return run.run_cell(cell, 2**31 + 17, seconds, False, require_chip=False,
                        config_overrides=SMALL,
                        engine_overrides=engine_overrides)


def test_result_line_and_a_correct_run():
    out = _cell()
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"admit_per_s", "admit_ms_p50",
                                   "admit_ms_p99", "setup_s"}
    assert out["checks"]["plans_checked"]["value"] >= 100
    assert set(out["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}


DAY_3H = {"horizon_hours": 3.0, "check_plans_min": 50}


@pytest.mark.parametrize("cell", ["rtx4090.10240.ramp", "rtx4090.128.day"])
def test_an_answer_altered_where_it_is_produced_is_caught(monkeypatch, cell):
    """Every fifth plan's placement tier is altered inside ``plan()``."""
    from repro.core import TopoScheduler

    plan = TopoScheduler.plan
    calls = []

    def altered(self, *a, **kw):
        txn = plan(self, *a, **kw)
        dec = txn.decision
        calls.append(1)
        if dec.placement is not None and len(calls) % 5 == 0:
            p = dec.placement
            dec.placement = type(p)(p.gpu_mask, p.cg_mask, (p.tier + 1) % 3)
        return txn

    monkeypatch.setattr(TopoScheduler, "plan", altered)
    if cell == "rtx4090.128.day":
        out = run.run_cell(cell, 2**31 + 3, 0.5, False, require_chip=False,
                           traffic_overrides=DAY_3H)
    else:
        out = _cell()
    assert out["correct"] is False
    assert out["checks"]["plans_wrong"]["value"] > 0


def test_device_state_left_unchanged_is_caught(monkeypatch):
    """From the start of the window, commits never reach the device-resident
    state: plans see a stale fleet."""
    from repro.core import DeviceClusterState

    mark_dirty = DeviceClusterState._mark_dirty
    enter = measure.Window.__enter__
    in_window = []

    def window_enter(self):
        in_window.append(True)
        return enter(self)

    def stale(self, node):
        if not in_window:
            mark_dirty(self, node)

    monkeypatch.setattr(measure.Window, "__enter__", window_enter)
    monkeypatch.setattr(DeviceClusterState, "_mark_dirty", stale)
    out = _cell()
    assert out["correct"] is False and out["failed"] > 0


def test_no_chip_and_no_program_exit_non_zero_with_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    args = ["--workload", "rtx4090.10240.ramp", "--seed", "1", "--seconds",
            "1", "--trace", "0"]
    got = subprocess.run([sys.executable, "bench/run.py", *args], cwd=ROOT,
                         env=env, capture_output=True, text=True, timeout=300)
    assert got.returncode != 0 and got.stdout == ""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env.pop("PYTHONPATH", None)
    got = subprocess.run([sys.executable, "bench/run.py", *args],
                         cwd=tmp_path, env=env, capture_output=True,
                         text=True, timeout=300)
    assert got.returncode != 0 and got.stdout == ""


_BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("name", [m["name"] for group in ("end_to_end",
                                                          "per_layer")
                                  for m in _BENCH[group]])
def test_every_metric_has_a_reader(name):
    assert callable(run.reader(name))


@pytest.mark.parametrize("traffic", sorted(
    p.stem for p in (ROOT / "bench" / "traffic").glob("*.json")))
def test_every_mix_names_a_loop(traffic):
    mix = json.loads((ROOT / "bench" / "traffic" / f"{traffic}.json")
                     .read_text())
    assert callable(run.loop(mix["loop"]).run)


def test_a_listed_metric_with_nothing_to_read_fails_the_run(monkeypatch):
    """A reader that finds nothing in a cell BENCHMARK.json lists it for
    stops the run: the metric is never dropped from the line in silence."""
    reader = run.reader
    monkeypatch.setattr(run, "reader", lambda name: (
        (lambda r: None) if name == "admit_ms_p99" else reader(name)))
    # a window long enough to check 100 plans on a loaded host, so that the
    # run is correct and the empty reader is what stops it
    with pytest.raises(RuntimeError, match="admit_ms_p99"):
        _cell()


def test_day_cell_checks_every_plan_of_a_repeat():
    out = run.run_cell("rtx4090.128.day", 2**31 + 3, 1.0, False,
                       require_chip=False,
                       traffic_overrides=DAY_3H)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"day_events_per_s", "setup_s"}
    checks = out["checks"]
    assert checks["plans_checked"]["value"] >= 50
    assert checks["repeats_differing"]["value"] == 0
