"""In-program spans and counters (`repro.obs`): recorded under `jax.profiler`
on the CPU at 128 nodes, with ``imp_batched`` and with ``imp_sharded`` on a
one-device mesh, and silent while off; the host-to-device calls a plan
makes, at 128 and 512 nodes."""
import random
from collections import Counter
from pathlib import Path

import jax
import pytest

from repro import obs
from repro.core import TopoScheduler, table3_workloads
from repro.core import preemption_jax as pj
from repro.core.cluster import DeviceClusterState, ViewDelta
from repro.core.colocation import (ColocationConfig, ColocationSim,
                                   default_policies)
from repro.core.simulator import build_scaled_cluster

WLS = {w.name: w for w in table3_workloads()}
#: online scale-ups that preempt on the saturated fleet, and offline work
#: the normal cycle places where a preemption left room
ORDER = ["A", "B", "D", "A", "C", "B"]
PLAN_STAGES = {"sched.sync", "sched.patch", "sched.route", "sched.dispatch",
               "sched.readback", "sched.decode", "sched.bind"}


@pytest.fixture
def obs_on():
    before = obs.enabled
    obs.enable(True)
    try:
        yield
    finally:
        obs.enable(before)


def _scheduler(engine):
    # shortlist k below the node count, so the shortlist path runs
    return TopoScheduler(build_scaled_cluster(128, seed=0), engine=engine,
                         shortlist_k=32)


def _key(dec):
    p = dec.placement
    return (dec.kind, dec.node, dec.victims, dec.hit,
            None if p is None else (p.gpu_mask, p.cg_mask, p.tier))


def _decide(sched, rounds):
    return [_key(sched.plan(WLS[name]).commit()) for name in ORDER * rounds]


def _side_uploads(monkeypatch) -> dict:
    """Records the ``h2d_calls`` of each state-sync flush and full upload
    and of each view-delta encode (`ViewDelta.device_rows`), by kind:
    they carry no plan argument."""
    side = {"flush": [], "full": [], "encoder": []}

    def wrap(cls, name, kind):
        real = cls.__dict__[name]

        def counted(self, *args):
            before = obs.counters["h2d_calls"]
            try:
                return real(self, *args)
            finally:
                side[kind].append(obs.counters["h2d_calls"] - before)

        monkeypatch.setattr(cls, name, counted)

    wrap(DeviceClusterState, "_scatter", "flush")
    wrap(DeviceClusterState, "_upload_full", "full")
    wrap(ViewDelta, "device_rows", "encoder")
    return side


def _side_total(side) -> int:
    return sum(sum(v) for v in side.values())


def _program_spans(log_dir):
    """``(name, start, end, stats)`` of every program span in the trace."""
    from jax.profiler import ProfileData

    (path,) = Path(log_dir).rglob("*.xplane.pb")
    data = ProfileData.from_file(str(path))
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for p in data.planes if not p.name.startswith("/device")
            for ln in p.lines for e in ln.events
            if e.name.startswith(("sched.", "day."))]


def _traced(log_dir, fn):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        return fn()
    finally:
        jax.profiler.stop_trace()


@pytest.mark.parametrize("engine", ["imp_batched", "imp_sharded"])
def test_plan_spans_nest_under_their_plan_and_share_its_seq(
        engine, tmp_path, obs_on):
    sched = _scheduler(engine)
    obs.enable(False)
    _decide(sched, 2)                    # warm-up: every bucket compiled
    obs.enable(True)
    dispatches = obs.counters["dispatches"]
    keys = _traced(tmp_path, lambda: _decide(sched, 1))
    assert {k[0] for k in keys} >= {"preempted"}
    spans = _program_spans(tmp_path)
    plans = [s for s in spans if s[0] == "sched.plan"]
    commits = [s for s in spans if s[0] == "sched.commit"]
    assert len(plans) == len(commits) == len(ORDER)
    assert [p[3]["seq"] for p in plans] == [c[3]["seq"] for c in commits]
    assert len({p[3]["seq"] for p in plans}) == len(plans)
    inner = [s for s in spans if s[0] not in ("sched.plan", "sched.commit")]
    assert {s[0] for s in inner} >= PLAN_STAGES
    for name, s0, s1, stats in inner:
        assert any(p0 <= s0 and s1 <= p1 for _, p0, p1, _ in plans), name
        if name == "sched.dispatch":
            assert stats["program"] != "f" and stats["program"].endswith(
                "_sharded") == (engine == "imp_sharded")
    for _, c0, c1, _ in commits:
        assert not any(p0 < c1 and c0 < p1 for _, p0, p1, _ in plans)
    assert obs.counters["dispatches"] - dispatches >= len(plans)


@pytest.mark.parametrize("engine", ["imp_batched", "imp_sharded"])
def test_off_records_nothing_and_decisions_match_on(engine):
    assert not obs.enabled
    before = dict(obs.counters)
    off = _decide(_scheduler(engine), 2)
    assert dict(obs.counters) == before
    obs.enable(True)
    try:
        on = _decide(_scheduler(engine), 2)
    finally:
        obs.enable(False)
    assert on == off
    assert obs.counters["dispatches"] > before.get("dispatches", 0)
    assert obs.counters["h2d_bytes"] > before.get("h2d_bytes", 0)


@pytest.mark.parametrize("num_nodes,horizon,tick,min_builds", [
    (12, 3.0, 1.0, 0),
    # three-hour ticks scale up in batches, through new batch sessions
    (24, 12.0, 3.0, 1),
])
def test_day_spans_hold_the_scheduler_spans(tmp_path, obs_on, num_nodes,
                                            horizon, tick, min_builds):
    cfg = ColocationConfig(num_nodes=num_nodes, seed=0, engine="imp_batched",
                           horizon_hours=horizon, tick_hours=tick)
    sim = ColocationSim(cfg, policies=default_policies(cfg))
    before = Counter(obs.counters)
    rep = _traced(tmp_path, sim.run)
    spans = _program_spans(tmp_path)
    names = {s[0] for s in spans}
    assert {"day.tick", "day.drain", "day.flush", "day.scale", "sched.plan",
            "sched.commit"} <= names
    day = [s for s in spans if s[0].startswith("day.")]
    for name, s0, s1, _ in spans:
        if name in ("sched.plan", "sched.commit"):
            assert any(d0 <= s0 and s1 <= d1 for _, d0, d1, _ in day)
    # each batch session is counted once, in the report and in the
    # program's counters, and opens inside a scale-up
    assert rep.batch_builds >= min_builds
    for name in ("batch_builds", "batch_reuses"):
        assert obs.counters[name] - before[name] == getattr(rep, name)
    scale = [s for s in spans if s[0] == "day.scale"]
    assert len(scale) == len(sim.auto.policies) * int(horizon / tick)
    sessions = [s for s in spans if s[0] == "sched.batch_session"]
    assert len(sessions) == rep.batch_builds
    for _, s0, s1, _ in sessions:
        assert any(d0 <= s0 and s1 <= d1 for _, d0, d1, _ in scale)


@pytest.mark.parametrize("engine", ["imp_batched", "imp_sharded"])
def test_route_counters_follow_the_changed_rows(engine, obs_on,
                                                monkeypatch):
    """On a saturated fleet planned through the shortlist, the first plan
    builds the class index; after it no plan scans the routing split or
    rebuilds the index, and the rep mask travels at most once a plan,
    inside the plan's one argument upload.  (k=64 of 128 rows: every
    shortlist winner is certain, so no plan takes the full-sweep fallback,
    whose adaptive width may scan.)"""
    side = _side_uploads(monkeypatch)
    sched = TopoScheduler(build_scaled_cluster(128, seed=0), engine=engine,
                          shortlist_k=64)
    before = Counter(obs.counters)
    sched.plan(WLS["A"]).commit()
    first = Counter(obs.counters)
    side0 = _side_total(side)
    assert first["rep_rebuilds"] - before["rep_rebuilds"] == 1
    keys = _decide(sched, 2)
    assert {k[0] for k in keys} >= {"preempted"}
    got = {name: obs.counters[name] - first[name]
           for name in ("route_scans", "rep_rebuilds", "h2d_calls")}
    assert got["route_scans"] == 0 and got["rep_rebuilds"] == 0
    assert got["h2d_calls"] - (_side_total(side) - side0) <= len(keys)
    dcs = sched.cluster.device_state(sharded=engine == "imp_sharded")
    assert dcs.fallbacks["shortlist_full_sweep"] == 0


@pytest.mark.parametrize("num_nodes", [128, 512])
@pytest.mark.parametrize("engine", ["imp_batched", "imp_sharded"])
def test_each_plan_uploads_its_arguments_at_most_once(engine, num_nodes,
                                                      obs_on, monkeypatch):
    """Seeded plans that place, preempt, force rep-mask corrections (plans
    on a shared view) and cross pending flushes: each single-request
    ``plan()`` makes at most one host-to-device call for its arguments,
    and a plan against unchanged state makes none at all.  The state
    sync's flush (one call: indices and rows packed) and the view-delta
    encoder's uploads are counted apart, as is the first plan's full
    upload.  At 512 rows the default K=128 shortlist runs, at 128 the
    resident program.  Decisions equal the host reference's."""
    side = _side_uploads(monkeypatch)
    forced = []
    real_forced = pj._forced_rows

    def forced_seen(dcs, patches):
        got = real_forced(dcs, patches)
        forced.append(len(got))
        return got

    monkeypatch.setattr(pj, "_forced_rows", forced_seen)
    sched = TopoScheduler(build_scaled_cluster(num_nodes, seed=0),
                          engine=engine)
    ref = TopoScheduler(build_scaled_cluster(num_nodes, seed=0), engine="imp")
    dcs = sched.cluster.device_state(sharded=engine == "imp_sharded")
    for name in "ABCD":          # request vectors, full upload, compiles
        sched.plan(WLS[name])
    rng = random.Random(num_nodes)
    uploads, keys, want = [], [], []

    def plan(wl, view=None):
        calls, s0 = obs.counters["h2d_calls"], _side_total(side)
        txn = sched.plan(wl, view=view)
        got = obs.counters["h2d_calls"] - calls
        uploads.append(got - (_side_total(side) - s0))
        assert uploads[-1] <= 1, (wl.name, got, side)
        return txn, got

    def commit(txn):
        if txn.decision.kind != "rejected":
            txn.commit()
        return _key(txn.decision)

    for _ in range(40):
        op = rng.choice(["commit", "commit", "again", "view", "release"])
        wls = [WLS[rng.choice("ABCD")] for _ in range(3 if op == "view"
                                                        else 1)]
        if op == "release":      # room the normal cycle can place into
            uid = rng.choice(sorted(u for u, i in sched.cluster.instances
                                    .items() if i.preemptible))
            sched.cluster.evict(uid)
            ref.cluster.evict(uid)
            continue
        if op == "view":
            view, rview = sched.cluster.view(), ref.cluster.view()
            txns = [plan(wl, view)[0] for wl in wls]
            keys += [commit(t) for t in txns]
            rtxns = [ref.plan(wl, view=rview) for wl in wls]
            want += [commit(t) for t in rtxns]
            continue
        if op == "again":        # unchanged state: nothing crosses
            plan(wls[0])
            ref.plan(wls[0])
            assert plan(wls[0])[1] == 0
        keys.append(commit(plan(wls[0])[0]))
        want.append(commit(ref.plan(wls[0])))
    assert keys == want
    kinds = {k[0] for k in keys}
    assert {"placed", "preempted"} <= kinds
    assert side["flush"] and set(side["flush"]) == {1}
    assert max(uploads) == 1
    assert dcs.fallbacks["shortlist_full_sweep"] == 0
    if num_nodes > 128:
        assert dcs.counts["shortlist_plans"] > 0 and max(forced) > 0
