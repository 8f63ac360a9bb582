"""The admission mixes at 128 nodes, driven through host ``imp`` (the mixes'
properties do not depend on the engine)."""
import json
from collections import Counter
from contextlib import nullcontext

import pytest

from bench import fleet, run

CFG = dict(fleet.load_config("rtx4090.10240"), num_nodes=128, chips=1)
QUIET = lambda name: nullcontext()  # noqa: E731


def _admission(traffic: str, seed: int):
    with open(fleet.ROOT / "traffic" / f"{traffic}.json") as f:
        mix = json.load(f)
    start = fleet.start_state(CFG, seed, mix["fill"])
    drv = run.loop("admission").Admission(CFG, mix, seed, start, {"engine": "imp"})
    return drv, Counter(name for name, *_ in start)


def _run(drv, decisions: int) -> None:
    for _ in range(decisions):
        drv.step(QUIET)
    while drv.pending:                    # let the last victims land
        drv.step(QUIET)


@pytest.mark.parametrize("seed", [1, 2**31 + 5])
def test_ramp_preempts_on_every_request_and_stays_stationary(seed):
    drv, start = _admission("ramp", seed)
    _run(drv, 150)
    by_source = Counter((d[0], d[1]) for d in drv.decisions)
    assert by_source[("request", "preempted")] > 0
    assert not {k for k in by_source if k[0] == "request"} - {
        ("request", "preempted")}
    assert Counter(drv.cluster.count_by_workload()) == start


def test_valley_never_preempts_and_stays_stationary():
    drv, start = _admission("valley", 4)
    _run(drv, 200)
    kinds = Counter(d[1] for d in drv.decisions)
    assert kinds["preempted"] == 0 and kinds["placed"] == len(drv.decisions)
    assert Counter(drv.cluster.count_by_workload()) == start


def test_every_seed_draws_the_same_mix():
    mixes = []
    for seed in (3, 4):
        drv, _ = _admission("valley", seed)
        _run(drv, 17 * 3)                 # three whole decks
        mixes.append(Counter(r[2] for r in drv.rec.log if r[0] == "plan"))
    assert mixes[0] == mixes[1] == Counter(A=3, B=6, C=30, D=12)
