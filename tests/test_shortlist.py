"""Equivalence-class + top-K shortlist sourcing: decision parity vs the
full sweep, fingerprint/representative maintenance, and mode semantics.

The shortlisted path must be *bit-identical* to the exact all-nodes subset
sweep in guaranteed mode — every test here pins that against the
``*_full`` oracle engines (same code with the shortlist front-end off).
``shortlist_k`` is forced tiny (4–8) so the prescreen actually prunes on
clusters far below the production default of 128.
"""
import random

import jax
import numpy as np
import pytest

from repro import obs
from repro.core import (Cluster, ColocationConfig, ShortlistConfig, SPECS,
                        TopoScheduler, run_day_cycle, table3_workloads)
from repro.core import preemption_jax as pj
from repro.core.cluster import (DeviceClusterState, SourcingContext,
                                ViewDelta, cluster_mesh, encode_row,
                                row_width)
from repro.core.placement import Placement
from repro.core.preemption_jax import NARROW_M
from repro.core.simulator import SimConfig, build_saturated_cluster
from repro.core.topology import ServerSpec
from repro.core.workload import TopoPolicy, WorkloadSpec

WL3 = {w.name: w for w in table3_workloads()}


def _decision_key(dec):
    return (dec.kind, dec.node, dec.victims,
            None if dec.placement is None else dec.placement.tier,
            dec.hit)


def _sat(num_nodes=24, seed=0):
    return build_saturated_cluster(SimConfig(num_nodes=num_nodes, seed=seed))


def _random_cluster(seed: int, spec, nodes: int = 6) -> Cluster:
    rng = random.Random(seed)
    cluster = Cluster(spec, nodes)
    for node in range(nodes):
        free = list(range(min(8, spec.num_gpus)))
        rng.shuffle(free)
        while free:
            if len(free) >= 2 and rng.random() < 0.4:
                g = [free.pop(), free.pop()]
                wl = WL3["C"]
            else:
                g = [free.pop()]
                wl = WL3["D"]
            if rng.random() < 0.2:
                continue
            mask = sum(1 << x for x in g)
            cluster.bind(wl, node, Placement(mask, mask, 0))
    return cluster


# ---------------------------------------------------------------------------------
# Guaranteed-mode decision parity vs the full-sweep oracle
# ---------------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 3, 11, 42])
@pytest.mark.parametrize("wl_name", ["A", "B", "C"])
def test_shortlist_single_plan_parity(seed, wl_name):
    decs = {}
    for engine, k in (("imp_batched", 6), ("imp_batched_full", 0)):
        sched = TopoScheduler(_sat(seed=seed), engine=engine, shortlist_k=k)
        decs[engine] = _decision_key(
            sched.plan(WL3[wl_name], allow_normal=False).decision)
    assert len(set(decs.values())) == 1, (seed, wl_name, decs)


@pytest.mark.parametrize("alpha", [0.0, 0.3, 0.5, 1.0])
def test_shortlist_parity_across_alpha(alpha):
    """The prescreen upper bound folds alpha into both of its terms; sweep
    it so tie-heavy regimes (alpha=0 and 1) hit the certainty check."""
    for seed in (1, 9):
        decs = {}
        for engine, k in (("imp_batched", 4), ("imp_batched_full", 0)):
            sched = TopoScheduler(_sat(seed=seed), engine=engine,
                                  alpha=alpha, shortlist_k=k)
            decs[engine] = _decision_key(
                sched.plan(WL3["B"], allow_normal=False).decision)
        assert len(set(decs.values())) == 1, (seed, alpha, decs)


@pytest.mark.parametrize("spec_name", sorted(SPECS))
def test_shortlist_parity_across_skus(spec_name):
    """Every SKU: the popcount tier tables baked into the prescreen must
    match the exact sweep's on all three server shapes.  Workloads are
    sized per-SKU (cores must be CoreGroup multiples)."""
    spec = SPECS[spec_name]
    cg = spec.coregroup_size
    victim = WorkloadSpec("v", priority=100, gpus_per_instance=1,
                          cores_per_instance=cg, preemptible=True)
    preemptor = WorkloadSpec("P", priority=1000, gpus_per_instance=2,
                             cores_per_instance=2 * cg, preemptible=False)
    rng = random.Random(7)

    def build():
        cluster = Cluster(spec, 6)
        for node in range(6):
            for g in range(spec.num_gpus):
                if rng.random() < 0.2:
                    continue
                cluster.bind(victim, node, Placement(1 << g, 1 << g, 0))
        return cluster

    state = rng.getstate()
    decs = {}
    for engine, k in (("imp_batched", 4), ("imp_batched_full", 0)):
        rng.setstate(state)
        sched = TopoScheduler(build(), engine=engine, shortlist_k=k)
        decs[engine] = _decision_key(
            sched.plan(preemptor, allow_normal=False).decision)
    assert len(set(decs.values())) == 1, (spec_name, decs)


def test_shortlist_parity_across_commit_sequences():
    """Commits mutate fingerprints incrementally; the rep set and prescreen
    must track them and keep agreeing with the full sweep."""
    seqs = {}
    for engine, k in (("imp_batched", 6), ("imp_batched_full", 0)):
        sched = TopoScheduler(_sat(seed=2), engine=engine, shortlist_k=k)
        seq = []
        for name in ("B", "C", "B", "B", "C", "B"):
            txn = sched.plan(WL3[name])
            seq.append(_decision_key(txn.decision))
            if txn.decision.kind != "rejected":
                txn.commit()
        seqs[engine] = seq
    assert seqs["imp_batched"] == seqs["imp_batched_full"]


def test_shortlist_parity_across_rollback():
    """Rollback restores prior placements with the original uids; the
    refreshed fingerprints must return to the pre-commit classes and the
    replans must match the oracle exactly."""
    seqs = {}
    for engine, k in (("imp_batched", 6), ("imp_batched_full", 0)):
        sched = TopoScheduler(_sat(seed=4), engine=engine, shortlist_k=k)
        seq = []
        txn = sched.plan(WL3["B"], allow_normal=False)
        seq.append(_decision_key(txn.decision))
        txn.commit()
        txn.rollback()
        for name in ("B", "C", "B"):
            t = sched.plan(WL3[name])
            seq.append(_decision_key(t.decision))
            if t.decision.kind != "rejected":
                t.commit()
        seqs[engine] = seq
    assert seqs["imp_batched"] == seqs["imp_batched_full"]


def test_shortlist_parity_in_plan_batch():
    """Batch sessions route patched (view-delta) nodes through the forced-
    row promotion: each patched node and a surviving member of its old
    class join the rep set, so the prescreen stays exact mid-batch."""
    batch = [WL3[n] for n in ("B", "B", "C", "B", "C", "B")]
    keys = {}
    for engine, k in (("imp_batched", 6), ("imp_batched_full", 0)):
        sched = TopoScheduler(_sat(seed=6), engine=engine, shortlist_k=k)
        keys[engine] = [_decision_key(t.decision)
                        for t in sched.plan_batch(batch)]
    assert keys["imp_batched"] == keys["imp_batched_full"]


def test_shortlist_parity_plan_batch_with_commits():
    seqs = {}
    for engine, k in (("imp_batched", 6), ("imp_batched_full", 0)):
        sched = TopoScheduler(_sat(seed=8), engine=engine, shortlist_k=k)
        seq = []
        for names in (("B", "C", "B"), ("C", "B", "B")):
            txns = sched.plan_batch([WL3[n] for n in names])
            for t in txns:
                if t.decision.kind != "rejected":
                    t.commit()
            seq.extend(_decision_key(t.decision) for t in txns)
        seqs[engine] = seq
    assert seqs["imp_batched"] == seqs["imp_batched_full"]


def test_shortlist_sharded_parity():
    """imp_sharded with the shard-local prescreen vs its full-sweep twin
    (runs on however many devices the host exposes, including one)."""
    seqs = {}
    for engine, k in (("imp_sharded", 6), ("imp_sharded_full", 0)):
        sched = TopoScheduler(_sat(seed=5), engine=engine, shortlist_k=k)
        seq = []
        for name in ("B", "C", "B", "C"):
            txn = sched.plan(WL3[name])
            seq.append(_decision_key(txn.decision))
            if txn.decision.kind != "rejected":
                txn.commit()
        seq.extend(_decision_key(t.decision)
                   for t in sched.plan_batch([WL3["B"]] * 4))
        seqs[engine] = seq
    assert seqs["imp_sharded"] == seqs["imp_sharded_full"]


# ---------------------------------------------------------------------------------
# Fingerprints and equivalence classes
# ---------------------------------------------------------------------------------

def test_fingerprint_incremental_matches_fresh():
    """After arbitrary commits, the incrementally-maintained fingerprints
    must equal a from-scratch rebuild's (same O(delta) invariant the rest
    of SourcingContext pins)."""
    cluster = _sat(seed=3)
    ctx = cluster.sourcing_context()
    ctx.refresh()
    sched = TopoScheduler(cluster, engine="imp_batched", shortlist_k=6)
    for name in ("B", "C", "B", "C"):
        txn = sched.plan(WL3[name])
        if txn.decision.kind != "rejected":
            txn.commit()
    ctx.refresh()
    fresh = SourcingContext(cluster)
    fresh.refresh()
    assert np.array_equal(ctx.fp, fresh.fp)


def test_fingerprint_identical_rows_collide_only_when_identical():
    """Nodes with identical resident rows share a fingerprint; binding one
    instance anywhere splits that node out of its class."""
    cluster = Cluster(SPECS["rtx4090"], 8)
    ctx = cluster.sourcing_context()
    ctx.refresh()
    assert len(set(ctx.fp.tolist())) == 1  # all-empty nodes: one class
    cluster.bind(WL3["D"], 3, Placement(1, 1, 0))
    ctx.refresh()
    fps = ctx.fp.tolist()
    assert len(set(fps)) == 2
    assert fps.count(fps[3]) == 1


def _uploaded_rep(dcs) -> np.ndarray:
    """The rep mask a delta-free shortlist plan uploads, sliced from its
    packed argument buffer on the device and unpacked as the program
    unpacks it."""
    pa = pj._patch_args(dcs, {})
    _, f, aux = pj._shortlist_plan_args(dcs, {}, WL3["A"].priority, pa)
    words = aux[pa.p * (1 + row_width(dcs.cap)) + f:]
    return np.asarray(pj._rep_mask(words, dcs.n_rows))


def test_rep_classes_one_lowest_index_rep_per_class():
    cluster = _random_cluster(1, SPECS["rtx4090"], nodes=10)
    dcs = cluster.device_state().sync()
    rep = dcs.rep_classes()
    n = cluster.num_nodes
    fp = dcs.mirror.fp[:n]
    # exactly one rep per distinct fingerprint, and it's the lowest index
    assert int(rep[:n].sum()) == len(set(fp.tolist()))
    for v in set(fp.tolist()):
        members = np.nonzero(fp == v)[0]
        assert rep[members[0]] and not rep[members[1:]].any()
    # the plan's upload carries the same bits
    assert np.array_equal(_uploaded_rep(dcs), rep)
    # cache: same version -> same arrays; new version -> recomputed
    rep2 = dcs.rep_classes()
    assert rep2 is rep
    free = [(nd, cluster.free_masks(nd)) for nd in range(n)]
    node, (fg, fc) = next((nd, m) for nd, m in free if m[0] & m[1])
    bit = (fg & fc) & -(fg & fc)     # lowest jointly-free GPU/CG pair
    cluster.bind(WL3["D"], node, Placement(bit, bit, 0))
    dcs.sync()
    rep3 = dcs.rep_classes()
    assert rep3 is not rep
    assert np.array_equal(_uploaded_rep(dcs), rep3)


# ---------------------------------------------------------------------------------
# Modes, knobs, routing
# ---------------------------------------------------------------------------------

def test_shortlist_best_effort_mode_returns_valid_plans():
    """Best-effort skips the certainty fallback: decisions must still be
    executable (commit cleanly), just not necessarily sweep-identical."""
    sched = TopoScheduler(_sat(seed=0), engine="imp_batched",
                          shortlist_k=4, shortlist_mode="best_effort")
    for name in ("B", "C", "B"):
        txn = sched.plan(WL3[name])
        if txn.decision.kind != "rejected":
            dec = txn.commit()
            assert dec.instance is not None


def test_shortlist_disabled_below_k():
    """Clusters at or below K rows skip the prescreen entirely (nothing to
    prune) — construction must not fail and plans must match the oracle."""
    a = TopoScheduler(_sat(num_nodes=8, seed=0), engine="imp_batched",
                      shortlist_k=128)
    b = TopoScheduler(_sat(num_nodes=8, seed=0), engine="imp_batched_full")
    assert (_decision_key(a.plan(WL3["B"]).decision)
            == _decision_key(b.plan(WL3["B"]).decision))


def test_shortlist_config_validation():
    with pytest.raises(ValueError):
        ShortlistConfig(k=128, mode="bogus")
    with pytest.raises(ValueError):
        ShortlistConfig(k=0)


def test_auto_engine_resolves_by_node_count():
    lo = TopoScheduler(_sat(num_nodes=8, seed=0), engine="auto")
    assert lo._provenance["engine"] == "imp_batched"
    assert lo._provenance["auto"] is True
    hi = TopoScheduler(_sat(num_nodes=24, seed=0), engine="auto",
                       auto_threshold=16)
    assert hi._provenance["engine"] == "imp_sharded"
    assert hi._provenance["auto_threshold"] == 16


def test_decision_carries_sourcing_provenance():
    sched = TopoScheduler(_sat(num_nodes=8, seed=0), engine="auto",
                          shortlist_k=64, shortlist_mode="guaranteed")
    dec = sched.plan(WL3["B"]).decision
    prov = dec.sourcing_provenance
    assert prov["engine"] == "imp_batched" and prov["auto"] is True
    assert prov["shortlist_k"] == 64
    assert prov["shortlist_mode"] == "guaranteed"
    # provenance is excluded from equality: parity comparisons stay valid
    assert "sourcing_provenance" not in repr(dec)


# ---------------------------------------------------------------------------------
# Day cycle under the shortlist front-end
# ---------------------------------------------------------------------------------

def test_day_cycle_guaranteed_shortlist_matches_full_sweep():
    """A short seeded day-cycle segment: guaranteed-mode shortlisting must
    reproduce the full sweep's day bit-for-bit (same preemptions, hits,
    placements, scheduled perf)."""
    base = dict(num_nodes=12, seed=7, horizon_hours=4.0, warmup=False,
                shortlist_k=6)
    sl = run_day_cycle(ColocationConfig(engine="imp_batched", **base))
    full = run_day_cycle(ColocationConfig(engine="imp_batched_full", **base))
    assert sl.preemptions == full.preemptions
    assert sl.hits == full.hits
    assert sl.placements == full.placements
    assert sl.scheduled_perf == pytest.approx(full.scheduled_perf)
    assert sl.offline_goodput == pytest.approx(full.offline_goodput)


# ---------------------------------------------------------------------------------
# Incremental class index against the from-scratch classes
# ---------------------------------------------------------------------------------

class _PaddedSharded(DeviceClusterState):
    """A state over a one-device mesh whose node axis pads to a multiple
    of four, as it does on a four-device mesh."""

    @property
    def n_rows(self) -> int:
        return -(-max(self.cluster.num_nodes, 1) // 4) * 4


def _rep_from_scratch(dcs) -> np.ndarray:
    """The lowest-index member of every fingerprint class, by `np.unique`."""
    n = dcs.cluster.num_nodes
    rep = np.zeros(dcs.n_rows, bool)
    _, first = np.unique(dcs.mirror.fp[:n], return_index=True)
    rep[first] = True
    return rep


def _forced_by_scan(dcs, patches) -> list[int]:
    """`_forced_rows` by a scan of the whole fingerprint column."""
    pset = {int(n) for n in patches}
    fp = dcs.mirror.fp
    forced = set(pset)
    for d in pset:
        for mbr in np.nonzero(fp == fp[d])[0]:
            if int(mbr) not in pset:
                forced.add(int(mbr))
                break
    return sorted(forced)


def _fits(cluster, inst) -> bool:
    """Whether an evicted instance's GPUs and CoreGroups are free again."""
    fg, fc = cluster.free_masks(inst.node)
    return (fg & inst.gpu_mask == inst.gpu_mask
            and fc & inst.cg_mask == inst.cg_mask)


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("engine", ["imp_batched", "imp_sharded"])
def test_class_index_matches_from_scratch(engine, seed, monkeypatch):
    """Seeded binds, evicts, restores, commits, rollbacks, plan_batch
    sessions and plans on a shared view (view-delta patch rows): after
    every sync the incrementally kept rep mask, on the host and unpacked
    from a plan's upload, equals the from-scratch mask bit for bit (23
    nodes: the sharded axis pads to 24, and the pad row stays False), and
    `_forced_rows` equals the scan, in every plan's own call too.  A step
    that changes most rows rebuilds the index."""
    sharded = engine == "imp_sharded"
    cluster = _sat(num_nodes=23, seed=seed)
    n = cluster.num_nodes
    if sharded:
        cluster._device_state = _PaddedSharded(
            cluster, mesh=cluster_mesh(jax.devices()[:1]))
    forced_calls = []
    real_forced = pj._forced_rows

    def forced_checked(dcs, patches):
        got = real_forced(dcs, patches)
        assert got == _forced_by_scan(dcs, patches)
        forced_calls.append(len(patches))
        return got

    monkeypatch.setattr(pj, "_forced_rows", forced_checked)
    sched = TopoScheduler(cluster, engine=engine, shortlist_k=4)
    dcs = cluster.device_state(sharded=sharded)
    rng = random.Random(seed)
    stash = []          # evicted instances, restored by later steps

    def check():
        dcs.sync()
        want = _rep_from_scratch(dcs)
        assert np.array_equal(dcs.rep_classes(), want)
        assert np.array_equal(_uploaded_rep(dcs), want)
        patched = dict.fromkeys(rng.sample(range(n), 3))
        assert real_forced(dcs, patched) == _forced_by_scan(dcs, patched)

    def victims():
        return [i for i in cluster.instances.values() if i.preemptible]

    def step(op):
        if op in ("commit", "rollback"):
            txn = sched.plan(WL3[rng.choice("ABCD")])
            if txn.decision.kind != "rejected":
                txn.commit()
                if op == "rollback":
                    txn.rollback()
        elif op in ("batch", "view"):
            wls = [WL3[rng.choice("ABC")] for _ in range(3)]
            if op == "batch":
                txns = sched.plan_batch(wls)
            else:       # plans on one shared view, without a session
                view = cluster.view()
                txns = [sched.plan(wl, view=view) for wl in wls]
            for t in txns:
                if t.decision.kind != "rejected":
                    t.commit()
        elif op == "evict":
            stash.append(cluster.evict(rng.choice(victims()).uid))
        elif op == "restore":
            fits = [i for i in stash if _fits(cluster, i)]
            if fits:
                inst = rng.choice(fits)
                stash.remove(inst)
                cluster.restore(inst)
        elif op == "bind":
            for node in rng.sample(range(n), n):
                fg, fc = cluster.free_masks(node)
                both = fg & fc
                if both:
                    bit = both & -both
                    cluster.bind(WL3["D"], node, Placement(bit, bit, 0))
                    break

    check()
    ops = ["commit", "rollback", "batch", "view", "evict", "restore", "bind"]
    for op in ops + [rng.choice(ops) for _ in range(8)]:
        step(op)
        check()
    # majority-dirty: one instance leaves every node that holds one
    by_node = {}
    for inst in cluster.instances.values():
        by_node.setdefault(inst.node, inst)
    assert 2 * len(by_node) >= n
    for inst in by_node.values():
        stash.append(cluster.evict(inst.uid))
    obs.enable(True)
    try:
        before = obs.counters["rep_rebuilds"]
        check()
        assert obs.counters["rep_rebuilds"] == before + 1
    finally:
        obs.enable(False)
    for op in ("restore", "view", "batch", "commit", "restore", "rollback"):
        step(op)
        check()
    assert any(forced_calls)    # view-delta patch rows reached the index


# ---------------------------------------------------------------------------------
# Routing gate at the dispatch's width
# ---------------------------------------------------------------------------------

BIG_CG = ServerSpec(name="bigcg", num_sockets=2, num_numa=8, num_cores=192,
                    num_gpus=8, coregroup_size=8)   # 24 CoreGroups


def _cpu_job(prio: int) -> WorkloadSpec:
    return WorkloadSpec(f"cpu{prio}", priority=prio, gpus_per_instance=0,
                        cores_per_instance=8, preemptible=True,
                        numa_policy=TopoPolicy.NONE,
                        socket_policy=TopoPolicy.NONE, critical=False,
                        kind="offline")


def _bind_victim(cluster, node: int, rng: random.Random) -> None:
    """One more preemptible instance on ``node`` of a cluster or a view: a
    D on a free GPU while the first four last, then CPU-only jobs (priority
    200 or 300) on the CoreGroups past the GPUs'."""
    bind = getattr(cluster, "plan_bind", None) or cluster.bind
    fg, fc = cluster.free_masks(node)
    low = fg & fc & 0xF
    if low:
        bit = low & -low
        bind(WL3["D"], node, Placement(bit, bit, 0))
        return
    cg = fc & ~0xFF
    bit = cg & -cg
    bind(_cpu_job(rng.choice((200, 300))), node, Placement(0, bit, 0))


def _gate_fleet(top: int, seed: int) -> Cluster:
    """20 nodes of 0..``top`` victims each, ``top`` reached on one node;
    past `NARROW_M`, one node is also truncated (18 > `MAX_DENSE_VICTIMS`)."""
    rng = random.Random(seed)
    cluster = Cluster(BIG_CG, 20)
    counts = [rng.randint(0, min(top, NARROW_M)) for _ in range(20)]
    counts[3] = 0
    counts[7] = top
    if top > NARROW_M:
        counts[11] = 18
    for node, k in enumerate(counts):
        for _ in range(k):
            _bind_victim(cluster, node, rng)
    return cluster


def _split_by_scan(dcs, patches, thresh, nodes, gate):
    """The routing split from every row's exact content."""
    ctx = dcs.mirror
    n = dcs.cluster.num_nodes
    elig = np.zeros(n, np.int64)
    bad = np.zeros(n, bool)
    for node in range(n):
        if node in patches:
            r = pj._patch_row(patches, node)
            elig[node] = ((r.vp < thresh) & r.stored).sum()
            bad[node] = r.overflow and r.next_priority < thresh
        else:
            elig[node] = ((ctx.vp[node] < thresh) & ctx.stored[node]).sum()
            bad[node] = ctx.overflow[node] and ctx.next_prio[node] < thresh
    allowed = np.zeros(n, bool)
    allowed[list(range(n)) if nodes is None else list(nodes)] = True
    ok = allowed & ~bad
    m_res = gate
    if m_res is None:
        m_res = (pj.MIN_M if int((ok & (elig > pj.MIN_M)).sum())
                 <= pj.MAX_ROWS_WIDE else NARROW_M)
    return (m_res,
            np.nonzero(ok & (elig > m_res) & (elig <= NARROW_M))[0].tolist(),
            np.nonzero(ok & (elig > NARROW_M))[0].tolist(),
            np.nonzero(allowed & bad)[0].tolist())


@pytest.mark.parametrize("level", [-1, 0, 2])
@pytest.mark.parametrize("kind", ["dict", "view_delta"])
@pytest.mark.parametrize("subset", [False, True])
@pytest.mark.parametrize("gate", [None, NARROW_M])
def test_routing_gate_matches_full_scan(gate, subset, kind, level):
    """`split_fused_nodes` equals the full scan on fleets whose largest row
    sits below, at and above the dispatch width, with no patch rows and
    with patch rows on both sides of it; the scan runs exactly when some
    row, stored or patched, holds more victims than the width."""
    w = pj.MIN_M if gate is None else gate
    cluster = _gate_fleet(w + level, seed=w + level)
    dcs = cluster.device_state().sync()
    assert dcs.count_max == w + level or w + level > NARROW_M
    rng = random.Random(level)
    nodes = sorted(rng.sample(range(20), 12)) + [3, 7] if subset else None
    low = cluster.view()                # patch rows at or under the width
    _bind_victim(low, 3, rng)
    low.plan_evict(next(i.uid for i in cluster.instances_on(7)
                        if i.preemptible))
    high = cluster.view()               # a patch row past the width
    for _ in range(w + 1):
        _bind_victim(high, 3, rng)
    obs.enable(True)
    try:
        for view in (cluster.view(), low, high):
            if kind == "dict":
                patches = {d: encode_row(view, d, dcs.cap)
                           for d in sorted(view.delta_nodes())}
            else:
                patches = ViewDelta(view, dcs.mirror, dcs.pending)
            big = max((pj._patch_row(patches, d).count for d in patches),
                      default=0)
            for thresh in (150, 250, 350, 600):
                scans = obs.counters["route_scans"]
                got = pj.split_fused_nodes(dcs, patches, thresh, nodes, gate)
                assert tuple(got) == _split_by_scan(dcs, patches, thresh,
                                                    nodes, gate)
                scanned = obs.counters["route_scans"] - scans
                assert scanned == int(dcs.count_max > w or big > w)
    finally:
        obs.enable(False)
