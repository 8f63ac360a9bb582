"""Each cell's control, run through the harness on the CPU at a size a test
run holds, reads not correct."""
import json

import pytest

from bench import fleet, run

SMALL = {"num_nodes": 128}


def _control(cell: str) -> dict:
    traffic = next(w["traffic"] for w in run.benchmark()["workloads"]
                   if w["name"] == cell)
    return json.loads(
        (fleet.ROOT / "traffic" / f"{traffic}.json").read_text())["control"]


@pytest.mark.parametrize("cell,traffic", [
    ("rtx4090.10240.ramp", None),
    ("rtx4090.128.day", {"horizon_hours": 8.0, "check_plans_min": 50}),
    ("rtx4090.10240.valley", None)])
def test_control_is_not_correct(cell, traffic):
    """Each cell's control (the mix's ``control`` key) is a path of the
    program that breaks a guarantee the configuration states: the
    best-effort shortlist (fixed K, no certainty fallback) loses the exact
    Eq. 2 optimum; the topology-unaware ``godel`` engine places first-fit
    instead of by tier and best fit."""
    out = run.run_cell(cell, 2**31 + 17, 2.0, False, require_chip=False,
                       config_overrides=None if traffic else SMALL,
                       traffic_overrides=traffic,
                       engine_overrides=_control(cell))
    assert out["correct"] is False
    assert out["checks"]["plans_wrong"]["value"] > 0
