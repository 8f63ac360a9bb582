"""Fused single-dispatch sourcing: winner parity, incremental arrays,
overflow fallback, and the pallas running-argmax outputs.

No hypothesis dependency: these must run in minimal environments too (the
fused path is the default ``imp_batched`` engine).
"""
import random

import numpy as np
import pytest

from repro.core import (Cluster, MAX_DENSE_VICTIMS, RTX4090_SERVER,
                        ServerSpec, TopoScheduler, table3_workloads)
from repro.core.cluster import SourcingContext
from repro.core.placement import Placement
from repro.core.workload import TopoPolicy, WorkloadSpec

WL3 = {w.name: w for w in table3_workloads()}
PARITY_ENGINES = ("imp", "imp_batched", "imp_sharded")


def random_cluster(seed: int, nodes: int = 5) -> Cluster:
    rng = random.Random(seed)
    cluster = Cluster(RTX4090_SERVER, nodes)
    for node in range(nodes):
        free = list(range(8))
        rng.shuffle(free)
        while free:
            if len(free) >= 2 and rng.random() < 0.4:
                g = [free.pop(), free.pop()]
                wl = WL3["C"]
            else:
                g = [free.pop()]
                wl = WL3["D"]
            if rng.random() < 0.2:
                continue  # leave a hole
            mask = sum(1 << x for x in g)
            cluster.bind(wl, node, Placement(mask, mask, 0))
    return cluster


def _decision_key(dec):
    return (dec.kind, dec.node, dec.victims,
            None if dec.placement is None else dec.placement.tier,
            dec.hit)


@pytest.mark.parametrize("seed", [0, 3, 7, 11, 42, 1234])
@pytest.mark.parametrize("wl_name", ["A", "B", "C"])
def test_fused_matches_python(seed, wl_name):
    """The fused engines' on-device Eq. 2 winner IS select_best's winner:
    same node, same victim set, same tier as the host engine ``imp``, on
    one device and over the sharded state."""
    decs = {}
    for engine in PARITY_ENGINES:
        cluster = random_cluster(seed)
        sched = TopoScheduler(cluster, engine=engine)
        decs[engine] = _decision_key(
            sched.plan(WL3[wl_name], allow_normal=False).decision)
    assert len(set(decs.values())) == 1, f"winner disagreement: {decs}"


@pytest.mark.parametrize("alpha", [0.0, 0.3, 1.0])
def test_fused_parity_across_alpha(alpha):
    """Eq. 1's priority/topology weighting happens on device for the fused
    engine — sweep alpha to cover the tie-break branches."""
    for seed in (1, 9, 77):
        decs = {}
        for engine in PARITY_ENGINES:
            cluster = random_cluster(seed)
            sched = TopoScheduler(cluster, engine=engine, alpha=alpha)
            decs[engine] = _decision_key(
                sched.plan(WL3["B"], allow_normal=False).decision)
        assert len(set(decs.values())) == 1, (seed, alpha, decs)


def test_fused_parity_in_plan_batch():
    """Later plans in a batch see earlier planned evictions/binds through
    the copy-on-write view; the vmapped batch session masks those delta
    nodes out of its precomputed tensors and re-sources only them, and
    must still agree with the host engine."""
    batch = [WL3["B"], WL3["B"], WL3["C"], WL3["B"]]
    keys = {}
    for engine in ("imp", "imp_batched"):
        cluster = random_cluster(21, nodes=4)
        sched = TopoScheduler(cluster, engine=engine)
        keys[engine] = [_decision_key(t.decision)
                        for t in sched.plan_batch(batch)]
    assert keys["imp"] == keys["imp_batched"]


@pytest.mark.parametrize("alpha", [0.0, 0.3, 0.5, 1.0])
def test_vmapped_plan_batch_parity_across_engines_and_alpha(alpha):
    """Acceptance pin: the vmapped `plan_batch` produces bitwise-identical
    decisions (node, victims, tier, hit) vs imp, on one device and over
    the sharded state, across the alpha sweep — 8 requests against one snapshot."""
    batch = [WL3[n] for n in ("B", "B", "C", "B", "C", "C", "B", "D")]
    for seed in (3, 42):
        keys = {}
        for engine in PARITY_ENGINES:
            cluster = random_cluster(seed)
            sched = TopoScheduler(cluster, engine=engine, alpha=alpha)
            keys[engine] = [_decision_key(t.decision)
                            for t in sched.plan_batch(batch)]
        assert len(set(map(tuple, keys.values()))) == 1, (seed, alpha, keys)


def test_vmapped_plan_batch_parity_across_commit_sequences():
    """Acceptance pin: repeated plan_batch → commit-all rounds stay
    decision-identical across engines (the resident state must track every
    commit incrementally, and each round's session snapshots it)."""
    seqs = {}
    for engine in ("imp", "imp_batched"):
        cluster = random_cluster(17)
        sched = TopoScheduler(cluster, engine=engine)
        seq = []
        for names in (("B", "C", "B"), ("C", "B"), ("B", "B", "C")):
            txns = sched.plan_batch([WL3[n] for n in names])
            for t in txns:
                t.commit()
            seq.extend(_decision_key(t.decision) for t in txns)
        seqs[engine] = seq
    assert len(set(map(tuple, seqs.values()))) == 1, seqs


def test_vmapped_plan_batch_matches_sequential_single_plans():
    """The batch session and the single-request resident path must agree
    candidate-for-candidate (same shared view, same decisions AND the same
    true evaluated-candidate counts)."""
    batch = [WL3["B"], WL3["C"], WL3["B"], WL3["B"]]
    from repro.core.cluster import ClusterView

    cluster_a = random_cluster(29)
    sched_a = TopoScheduler(cluster_a, engine="imp_batched")
    batched = sched_a.plan_batch(batch)

    cluster_b = random_cluster(29)
    sched_b = TopoScheduler(cluster_b, engine="imp_batched")
    view = ClusterView(cluster_b)
    singles = [sched_b.plan(wl, view=view) for wl in batch]

    assert ([_decision_key(t.decision) for t in batched]
            == [_decision_key(t.decision) for t in singles])
    assert ([t.decision.num_candidates for t in batched]
            == [t.decision.num_candidates for t in singles])


def test_fused_filter_rejects_identically_to_host_filter():
    """Guaranteed Filtering fused into the dispatch must reject exactly when
    the host filter loop does — here nothing on the cluster is preemptible
    below the preemptor, so every engine must return kind=rejected."""
    blocker = WorkloadSpec("hi", priority=9000, gpus_per_instance=2,
                           cores_per_instance=16, preemptible=False)
    cluster = Cluster(RTX4090_SERVER, 2)
    for node in range(2):
        for i in range(4):
            mask = 0b11 << (2 * i)
            cluster.bind(blocker, node, Placement(mask, mask, 0))
    for engine in PARITY_ENGINES:
        dec = TopoScheduler(cluster, engine=engine).plan(
            WL3["B"], allow_normal=False).decision
        assert dec.rejected, engine


def test_fused_parity_across_commits():
    """Sequential commit-then-plan: the context must incrementally track the
    mutations the commits make (dirty-node refresh, not a full rebuild)."""
    seqs = {}
    for engine in ("imp", "imp_batched"):
        cluster = random_cluster(5, nodes=4)
        sched = TopoScheduler(cluster, engine=engine)
        seq = []
        for wl_name in ("B", "C", "B", "B", "C"):
            dec = sched.plan(WL3[wl_name], allow_normal=False).commit()
            seq.append(_decision_key(dec))
        seqs[engine] = seq
    assert seqs["imp"] == seqs["imp_batched"]


# ---------------------------------------------------------------------------------
# SourcingContext invalidation semantics
# ---------------------------------------------------------------------------------

def _context_state(ctx):
    return {name: getattr(ctx, name).copy()
            for name in ("free_gpu", "free_cg", "vg", "vc", "vp", "vu",
                         "rank", "stored", "count", "overflow", "next_prio")}


def _assert_rows_equal(incremental, fresh):
    assert np.array_equal(incremental.stored, fresh.stored)
    for name, arr in _context_state(fresh).items():
        got = getattr(incremental, name)
        if arr.ndim == 2 and name != "stored":
            # slots beyond `count` are padding: compare stored content only
            assert np.array_equal(got[fresh.stored], arr[fresh.stored]), name
        else:
            assert np.array_equal(got, arr), name


def test_sourcing_context_tracks_mutations_incrementally():
    cluster = random_cluster(13, nodes=4)
    ctx = cluster.sourcing_context()
    ctx.refresh()
    # commit a preemption through the scheduler: evictions + a bind
    sched = TopoScheduler(cluster, engine="imp_batched")
    txn = sched.plan(WL3["B"], allow_normal=False)
    txn.commit()
    assert ctx._dirty, "commit must mark nodes dirty via invalidate_node"
    ctx.refresh()
    fresh = SourcingContext(cluster)
    fresh.refresh()
    _assert_rows_equal(ctx, fresh)
    # rollback restores the exact prior rows
    txn.rollback()
    ctx.refresh()
    fresh2 = SourcingContext(cluster)
    fresh2.refresh()
    _assert_rows_equal(ctx, fresh2)


def test_sourcing_context_rank_orders_uids():
    cluster = random_cluster(3, nodes=2)
    ctx = cluster.sourcing_context()
    ctx.refresh()
    for node in range(cluster.num_nodes):
        cnt = int(ctx.count[node])
        uids = ctx.vu[node, :cnt]
        ranks = ctx.rank[node, :cnt]
        if cnt:
            assert sorted(ranks) == list(range(cnt))
            assert np.array_equal(np.argsort(np.argsort(uids)), ranks)


# ---------------------------------------------------------------------------------
# Overflow (> MAX_DENSE_VICTIMS victims on one node) falls back, not crashes
# ---------------------------------------------------------------------------------

BIG_CG_SERVER = ServerSpec(
    name="bigcg", num_sockets=2, num_numa=8, num_cores=192, num_gpus=8,
    coregroup_size=8)   # 24 CoreGroups: room for > 16 victims on one node

CPU_JOB = WorkloadSpec("cpu-only", priority=200, gpus_per_instance=0,
                       cores_per_instance=8, preemptible=True,
                       numa_policy=TopoPolicy.NONE,
                       socket_policy=TopoPolicy.NONE, critical=False,
                       kind="offline")


def _overflow_cluster() -> Cluster:
    """One node with 18 preemptible victims (> MAX_DENSE_VICTIMS): GPUs held
    by 4 C instances, plus 14 cpu-only jobs."""
    cluster = Cluster(BIG_CG_SERVER, 1)
    for i in range(4):
        gmask = 0b11 << (2 * i)
        cmask = 0b11 << (2 * i)
        cluster.bind(WL3["C"], 0, Placement(gmask, cmask, 0))
    for i in range(14):
        cmask = 1 << (8 + i)
        cluster.bind(CPU_JOB, 0, Placement(0, cmask, 0))
    return cluster


def _wide_cluster() -> Cluster:
    """Node 0 holds 10 victims (wide m=16 bucket, NOT overflow); node 1 is a
    normal narrow node — exercises the per-bucket dispatch grouping."""
    cluster = Cluster(BIG_CG_SERVER, 2)
    for i in range(4):
        gmask = 0b11 << (2 * i)
        cmask = 0b11 << (2 * i)
        cluster.bind(WL3["C"], 0, Placement(gmask, cmask, 0))
    for i in range(6):
        cluster.bind(CPU_JOB, 0, Placement(0, 1 << (8 + i), 0))
    for i in range(6):
        cluster.bind(WL3["D"], 1, Placement(1 << i, 1 << i, 0))
    return cluster


def test_wide_bucket_nodes_dispatch_separately_with_parity():
    cluster = _wide_cluster()
    assert 8 < len(cluster.victims_on(0, WL3["B"].priority)) <= MAX_DENSE_VICTIMS
    want = _decision_key(TopoScheduler(_wide_cluster(), engine="imp")
                         .plan(WL3["B"], allow_normal=False).decision)
    got = _decision_key(TopoScheduler(cluster, engine="imp_batched")
                        .plan(WL3["B"], allow_normal=False).decision)
    assert got == want


def test_cross_tier_exact_score_tie_breaks_by_victim_count():
    """Adversarial Eq. 1 tie across tiers: (tier 0, prio_sum 2, k=1) and
    (tier 1, prio_sum 1, k=2) both score exactly 0.75 at alpha=0.5.
    select_best breaks the tie by fewer victims; the fused device chain
    must not let its priority-sum refinement pick the other node."""
    blocker = WorkloadSpec("blk", priority=5000, gpus_per_instance=7,
                           cores_per_instance=56, preemptible=False)
    v_lo = WorkloadSpec("v2", priority=2, gpus_per_instance=1,
                        cores_per_instance=8, preemptible=True)
    v_a = WorkloadSpec("v0", priority=0, gpus_per_instance=1,
                       cores_per_instance=0, preemptible=True)
    v_b = WorkloadSpec("v1", priority=1, gpus_per_instance=0,
                       cores_per_instance=8, preemptible=True)
    preemptor = WorkloadSpec("P", priority=1000, gpus_per_instance=1,
                             cores_per_instance=8, preemptible=False,
                             numa_policy=TopoPolicy.BEST_EFFORT)

    def build():
        cluster = Cluster(RTX4090_SERVER, 2)
        # node 0: evicting the prio-2 victim frees gpu0+cg0 (NUMA 0, tier 0)
        cluster.bind(v_lo, 0, Placement(1 << 0, 1 << 0, 0))
        cluster.bind(blocker, 0, Placement(0xFE, 0xFE, 0))
        # node 1: two victims (prio 0 + prio 1) free gpu0 + cg1 — same
        # socket, different NUMA: tier 1 at prio_sum 1
        cluster.bind(v_a, 1, Placement(1 << 0, 0, 0))
        cluster.bind(v_b, 1, Placement(0, 1 << 1, 0))
        cluster.bind(blocker, 1, Placement(0xFE, 0xFD, 0))
        return cluster

    decs = {}
    for engine in ("imp", "imp_batched"):
        sched = TopoScheduler(build(), engine=engine, alpha=0.5)
        decs[engine] = _decision_key(
            sched.plan(preemptor, allow_normal=False).decision)
    assert len(set(decs.values())) == 1, decs
    assert decs["imp_batched"][1] == 0        # fewer victims -> node 0
    assert len(decs["imp_batched"][2]) == 1


def test_fused_num_candidates_matches_legacy():
    """The device counts every feasible min-k subset; the decision must
    report that count, not the shortlist length: the number of candidates
    the host engine ``imp`` lists."""
    for seed in (0, 7):
        decs = {}
        for engine in ("imp", "imp_batched"):
            cluster = random_cluster(seed)
            sched = TopoScheduler(cluster, engine=engine)
            decs[engine] = sched.plan(WL3["B"], allow_normal=False).decision
        assert (decs["imp_batched"].num_candidates
                == decs["imp"].num_candidates > 0)


def test_truncated_row_stays_dense_when_eligible_victims_fit():
    """A node with > MAX_DENSE_VICTIMS preemptible instances whose ELIGIBLE
    victims (priority < preemptor) fit the stored prefix must stay on the
    fused fast path, not fall back to per-node python sourcing."""
    from repro.core.preemption_jax import split_fused_nodes

    cpu500 = WorkloadSpec("cpu500", priority=500, gpus_per_instance=0,
                          cores_per_instance=8, preemptible=True,
                          numa_policy=TopoPolicy.NONE,
                          socket_policy=TopoPolicy.NONE, critical=False)
    blocker = WorkloadSpec("blk", priority=5000, gpus_per_instance=6,
                           cores_per_instance=48, preemptible=False)
    mid = WorkloadSpec("mid", priority=300, gpus_per_instance=1,
                       cores_per_instance=8, preemptible=False)

    def build():
        cluster = Cluster(BIG_CG_SERVER, 1)
        for i in range(2):
            cluster.bind(WL3["D"], 0, Placement(1 << i, 1 << i, 0))
        cluster.bind(blocker, 0, Placement(0xFC, 0xFC, 0))
        for i in range(16):
            cluster.bind(cpu500, 0, Placement(0, 1 << (8 + i), 0))
        return cluster

    cluster = build()
    assert len([i for i in cluster.instances_on(0) if i.preemptible]) \
        > MAX_DENSE_VICTIMS
    dcs = cluster.device_state().sync()
    split = split_fused_nodes(dcs, {}, mid.priority)
    # truncated row, still dense: no python fallback, no 2^16 re-dispatch
    assert split.overflow == [] and split.wide == []
    want = _decision_key(TopoScheduler(build(), engine="imp")
                         .plan(mid, allow_normal=False).decision)
    got = _decision_key(TopoScheduler(cluster, engine="imp_batched")
                        .plan(mid, allow_normal=False).decision)
    assert got == want == ("preempted", 0, got[2], got[3], got[4])


@pytest.mark.parametrize("engine",
                         ["imp_batched", "imp_sharded", "imp_pallas"])
def test_overflow_node_falls_back_instead_of_crashing(engine):
    from repro.core.preemption import flextopo_imp

    cluster = _overflow_cluster()
    assert len(cluster.victims_on(0, WL3["B"].priority)) > MAX_DENSE_VICTIMS
    ref_cluster = _overflow_cluster()
    ref = TopoScheduler(ref_cluster, engine="imp")
    want = _decision_key(ref.plan(WL3["B"], allow_normal=False).decision)
    sched = TopoScheduler(cluster, engine=engine)
    got = _decision_key(sched.plan(WL3["B"], allow_normal=False).decision)
    assert got == want
    assert flextopo_imp(cluster, WL3["B"], 0)  # sanity: preemption feasible


# ---------------------------------------------------------------------------------
# Pallas running argmax + interpret flag plumbing
# ---------------------------------------------------------------------------------

def test_pallas_running_argmax_matches_host_reduction():
    jnp = pytest.importorskip("jax.numpy")
    from repro.kernels.topo_score import (K_INFEASIBLE, TopoRequest,
                                          topo_score_argmax_pallas)

    spec = RTX4090_SERVER
    rng = np.random.default_rng(7)
    n = 1500   # > one (8, 128) tile, not a tile multiple
    cg = rng.integers(0, spec.all_gpu_mask + 1, n).astype(np.int32)
    cc = rng.integers(0, spec.all_cg_mask + 1, n).astype(np.int32)
    pr = rng.integers(0, 3000, n).astype(np.int32)
    kk = rng.integers(0, 6, n).astype(np.int32)
    req = TopoRequest(2, 2, 1, alpha=0.5)
    tier, score, kmin, btier, bscore, bidx = topo_score_argmax_pallas(
        jnp.asarray(cg), jnp.asarray(cc), jnp.asarray(pr), jnp.asarray(kk),
        spec, req)
    tier, score = np.asarray(tier), np.asarray(score)
    kmin, btier = np.asarray(kmin), np.asarray(btier)
    bscore, bidx = np.asarray(bscore), np.asarray(bidx)
    tile = 8 * 128
    for t in range(len(kmin)):
        lo, hi = t * tile, min((t + 1) * tile, n)
        feas = tier[lo:hi] < 3
        if not feas.any():
            assert kmin[t] == K_INFEASIBLE
            continue
        k_t = kk[lo:hi][feas].min()
        assert kmin[t] == k_t
        sel = feas & (kk[lo:hi] == k_t)
        t_t = tier[lo:hi][sel].min()
        assert btier[t] == t_t
        sel &= tier[lo:hi] == t_t
        s_t = score[lo:hi][sel].max()
        assert bscore[t] == pytest.approx(s_t)
        sel &= score[lo:hi] == s_t
        assert bidx[t] == lo + int(np.nonzero(sel)[0][0])


def test_pallas_filtering_mask_input_masks_lanes():
    """Lanes zeroed by the kernel's filtering-mask input must report tier 3
    / -inf score and never win the per-tile argmax."""
    jnp = pytest.importorskip("jax.numpy")
    from repro.kernels.topo_score import (K_INFEASIBLE, TopoRequest,
                                          topo_score_argmax_pallas)

    spec = RTX4090_SERVER
    rng = np.random.default_rng(11)
    n = 1500
    cg = rng.integers(0, spec.all_gpu_mask + 1, n).astype(np.int32)
    cc = rng.integers(0, spec.all_cg_mask + 1, n).astype(np.int32)
    pr = rng.integers(0, 3000, n).astype(np.int32)
    kk = rng.integers(0, 6, n).astype(np.int32)
    ok = (rng.random(n) < 0.5).astype(np.int32)
    req = TopoRequest(2, 2, 1, alpha=0.5)
    base = topo_score_argmax_pallas(
        jnp.asarray(cg), jnp.asarray(cc), jnp.asarray(pr), jnp.asarray(kk),
        spec, req)
    masked = topo_score_argmax_pallas(
        jnp.asarray(cg), jnp.asarray(cc), jnp.asarray(pr), jnp.asarray(kk),
        spec, req, ok=jnp.asarray(ok))
    tier_b, tier_m = np.asarray(base[0]), np.asarray(masked[0])
    score_m = np.asarray(masked[1])
    off = ok == 0
    assert np.all(tier_m[off] == 3) and np.all(np.isneginf(score_m[off]))
    assert np.array_equal(tier_m[~off], tier_b[~off])
    # the per-tile argmax only ever picks unmasked lanes
    kmin, bidx = np.asarray(masked[2]), np.asarray(masked[5])
    for t in range(len(kmin)):
        if kmin[t] != K_INFEASIBLE:
            assert ok[bidx[t]] == 1


def test_pallas_engine_parity_with_mixed_eligibility():
    """A node mixing eligible and ineligible victims must still match the
    exact python engine (the eligible set is a prefix slice; the kernel's
    filtering mask guards the lanes)."""
    lo = WorkloadSpec("lo", priority=100, gpus_per_instance=1,
                      cores_per_instance=8, preemptible=True)
    hi = WorkloadSpec("hi", priority=2000, gpus_per_instance=1,
                      cores_per_instance=8, preemptible=True)
    mid = WorkloadSpec("mid", priority=900, gpus_per_instance=2,
                       cores_per_instance=16, preemptible=False)

    def build():
        cluster = Cluster(RTX4090_SERVER, 1)
        for i in range(4):
            cluster.bind(lo if i % 2 else hi, 0,
                         Placement(1 << i, 1 << i, 0))
        cluster.bind(mid, 0, Placement(0b11 << 4, 0b11 << 4, 0))
        return cluster

    want = _decision_key(TopoScheduler(build(), engine="imp")
                         .plan(mid, allow_normal=False).decision)
    got = _decision_key(TopoScheduler(build(), engine="imp_pallas")
                        .plan(mid, allow_normal=False).decision)
    assert got == want


def test_pallas_interpret_env_flag(monkeypatch):
    """Interpret mode follows the backend alone: the environment can no
    longer force the Mosaic interpreter onto a TPU (nor compiled kernels
    onto the CPU)."""
    import jax

    from repro.kernels import topo_score

    want = jax.default_backend() != "tpu"
    for value in ("1", "0", "auto", "true"):
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", value)
        assert topo_score._interpret_default() is want
