"""Device-side placement scorer: randomized host-vs-device parity, the
fused normal cycle, persistent batch sessions, and the `_lowest_bits`
feasibility fix.

Seeded-random loops, no hypothesis dependency (the fused placement path is
the default ``imp_batched`` engine and must be testable in minimal
environments).
"""
import random

import numpy as np
import pytest

from repro.core import (Cluster, RTX4090_SERVER, TopoScheduler,
                        table3_workloads)
from repro.core.placement import (INFEASIBLE, _lowest_bits, best_tier, place,
                                  place_blind)
from repro.core.placement_jax import (device_best_tier, device_place,
                                      device_place_blind)
from repro.core.topology import SPECS
from repro.core.workload import TABLE3_INITIAL_INSTANCES, WorkloadSpec

WL3 = {w.name: w for w in table3_workloads()}


def _partial_cluster(seed: int, nodes: int = 6, fill: float = 0.6) -> Cluster:
    """A partially-drained cluster: some nodes keep normal-cycle room."""
    from repro.core.simulator import SimConfig, build_saturated_cluster

    counts = {k: max(0, round(v * nodes / 100.0 * fill))
              for k, v in TABLE3_INITIAL_INSTANCES.items()}
    return build_saturated_cluster(SimConfig(num_nodes=nodes, seed=seed),
                                   counts=counts)


def _decision_key(dec):
    return (dec.kind, dec.node, dec.victims, dec.hit,
            None if dec.placement is None else
            (dec.placement.gpu_mask, dec.placement.cg_mask,
             dec.placement.tier))


# ---------------------------------------------------------------------------------
# Randomized host-vs-device place()/best_tier/place_blind equivalence
# ---------------------------------------------------------------------------------

@pytest.mark.parametrize("spec_name", sorted(SPECS))
@pytest.mark.parametrize("seed", [0, 1, 7])
def test_place_and_tier_parity_all_skus(spec_name, seed):
    """Across every ServerSpec SKU: random partially-drained free masks and
    random (gpus, cgs, bundle) asks must tier AND place bitwise-identically
    on host and device (masks included, CPU-only and no-bundle covered)."""
    spec = SPECS[spec_name]
    rng = random.Random(seed)
    for _ in range(150):
        fg = rng.randrange(0, spec.all_gpu_mask + 1)
        fc = rng.randrange(0, spec.all_cg_mask + 1)
        ng = rng.randrange(0, spec.num_gpus + 1)
        nc = rng.randrange(0, spec.num_coregroups + 1)
        bundle = rng.random() < 0.7
        args = (spec, fg, fc, ng, nc, bundle)
        assert best_tier(*args) == device_best_tier(*args), args
        assert place(*args) == device_place(*args), args
        assert (place_blind(spec, fg, fc, ng, nc)
                == device_place_blind(spec, fg, fc, ng, nc)), args


def test_device_place_commits_best_tier_masks():
    spec = RTX4090_SERVER
    p = device_place(spec, spec.all_gpu_mask, spec.all_cg_mask, 2, 2)
    assert p is not None and p.tier == 1
    assert p == place(spec, spec.all_gpu_mask, spec.all_cg_mask, 2, 2)
    assert device_place(spec, 0, 0, 1, 1) is None
    assert device_best_tier(spec, 0, 0, 1, 1) == INFEASIBLE


# ---------------------------------------------------------------------------------
# Normal-cycle decision parity: host imp vs the fused chained dispatch
# ---------------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 3, 11, 42])
def test_normal_cycle_parity_imp_vs_fused(seed):
    """On a cluster with free room, plan() must resolve in the normal cycle
    with the SAME node, masks, tier and hit for the host loop and the
    single chained dispatch."""
    for name in ("A", "B", "C", "D"):
        decs = {}
        for engine in ("imp", "imp_batched"):
            sched = TopoScheduler(_partial_cluster(seed), engine=engine)
            decs[engine] = _decision_key(sched.plan(WL3[name]).decision)
        assert decs["imp"] == decs["imp_batched"], (seed, name, decs)
        if name in ("C", "D"):    # small asks always fit at 60% fill
            assert decs["imp"][0] == "placed"


@pytest.mark.parametrize("seed", [2, 9])
def test_normal_cycle_parity_under_commit_rollback(seed):
    """Commit/rollback sequences: the resident state must track every
    mutation and the chained dispatch must keep agreeing with imp."""
    seqs = {}
    for engine in ("imp", "imp_batched"):
        sched = TopoScheduler(_partial_cluster(seed, nodes=5), engine=engine)
        seq = []
        pending = []
        for step, name in enumerate(("C", "B", "D", "C", "B", "D", "C")):
            txn = sched.plan(WL3[name])
            txn.commit()
            pending.append(txn)
            seq.append(_decision_key(txn.decision))
            if step % 3 == 2:            # roll the last two back
                pending.pop().rollback()
                pending.pop().rollback()
        seqs[engine] = seq
    assert seqs["imp"] == seqs["imp_batched"], seqs


def _normal_cycle_static_and_traced(nodestate, spec, ng, nc, cpb):
    """`normal_cycle_core` jitted with the request baked in as constants
    and with it traced: the two compile differently and must agree."""
    import jax

    from repro.core.placement_jax import normal_cycle_core

    static = jax.jit(
        lambda x: normal_cycle_core(x, ng, nc, cpb, spec=spec))(nodestate)
    traced = jax.jit(
        lambda x, a, b, c: normal_cycle_core(x, a, b, c, spec=spec))(
        nodestate, ng, nc, cpb)
    return [int(v) for v in static], [int(v) for v in traced]


def test_normal_cycle_static_vs_traced_request():
    """Seed 2, 5 nodes, after committing C then B on host imp: the normal
    cycle for D (request (1, 1, 0)) places on node 2 with masks (2, 2).
    XLA:CPU once compiled the constant-request program to gpu_mask 0 while
    the traced one (and host ``place()``) gave 2."""
    sched = TopoScheduler(_partial_cluster(2, nodes=5), engine="imp")
    for name in ("C", "B"):
        sched.plan(WL3[name]).commit()
    host = sched.plan(WL3["D"]).decision
    assert host.kind == "placed"
    spec = sched.cluster.spec
    nodestate = sched.cluster.device_state().sync().nodestate
    static, traced = _normal_cycle_static_and_traced(nodestate, spec, 1, 1, 0)
    want = [1, host.node, host.placement.tier, host.placement.gpu_mask,
            host.placement.cg_mask]
    assert want == [1, 2, 0, 2, 2]
    assert static == traced == want


@pytest.mark.parametrize("req", [(1, 0, 0), (2, 4, 0), (3, 4, 0), (4, 4, 0),
                                 (2, 4, 2)])
def test_normal_cycle_static_vs_traced_random_state(req):
    """Random free masks on 7 nodes: the constant-request and the
    traced-request programs agree for asks that once compiled apart."""
    spec = RTX4090_SERVER
    rng = random.Random(0)
    ns = np.zeros((5, 7), np.int32)
    for j in range(7):
        ns[0, j] = rng.randrange(0, spec.all_gpu_mask + 1)
        ns[1, j] = rng.randrange(0, spec.all_cg_mask + 1)
        ns[2, j] = j
    static, traced = _normal_cycle_static_and_traced(ns, spec, *req)
    assert static == traced, (req, static, traced)


def test_fused_plan_falls_through_to_preemption_with_masks():
    """Saturated cluster: the chained dispatch must take the preemptive
    branch and return the same victims AND placement masks as imp."""
    from tests.test_fused_sourcing import random_cluster

    kinds = set()
    for seed in (0, 7, 42):
        decs = {}
        for engine in ("imp", "imp_batched"):
            sched = TopoScheduler(random_cluster(seed), engine=engine)
            decs[engine] = _decision_key(sched.plan(WL3["B"]).decision)
        assert decs["imp"] == decs["imp_batched"], (seed, decs)
        kinds.add(decs["imp"][0])
    assert "preempted" in kinds   # the chained cond took the preempt branch


def test_schedule_only_uses_normal_dispatch():
    """allow_preempt=False on the fused engine: placed on free clusters,
    rejected (never preempted) on saturated ones — identically to imp."""
    for seed in (1, 5):
        for build, want in ((_partial_cluster, "placed"),):
            decs = {}
            for engine in ("imp", "imp_batched"):
                sched = TopoScheduler(build(seed), engine=engine)
                decs[engine] = _decision_key(
                    sched.plan(WL3["B"], allow_preempt=False).decision)
            assert decs["imp"] == decs["imp_batched"]
            assert decs["imp"][0] == want
    from tests.test_fused_sourcing import random_cluster

    dec = TopoScheduler(random_cluster(3), engine="imp_batched").plan(
        WL3["B"], allow_preempt=False).decision
    assert dec.rejected


def test_blind_ablation_keeps_host_placement_path():
    """topology_aware_placement=False must not consume device placements
    (the device scorer is the topology-aware allocator)."""
    decs = {}
    for engine in ("imp", "imp_batched"):
        sched = TopoScheduler(_partial_cluster(4), engine=engine,
                              topology_aware_placement=False)
        assert not sched._fused_place
        decs[engine] = _decision_key(sched.plan(WL3["C"]).decision)
    assert decs["imp"] == decs["imp_batched"]


def test_device_state_exposes_numa_socket_slices():
    """`DeviceClusterState.slices` hands out the per-SKU slice layout the
    placement scorer consumes (cached: same object as spec_slices)."""
    from repro.core.placement_jax import spec_slices

    cluster = _partial_cluster(0, nodes=2)
    spec = cluster.spec
    sl = cluster.device_state().slices
    assert sl is spec_slices(spec)
    assert sl.scope_mask.shape == (spec.num_numa + spec.num_sockets + 1,
                                   spec.num_numa)
    assert sl.g_bits.shape == (spec.num_gpus,)
    assert int(sl.scope_tier[-1]) == 2    # the global (cross-socket) scope


# ---------------------------------------------------------------------------------
# Persistent BatchSourcingSession
# ---------------------------------------------------------------------------------

def test_persistent_session_reused_across_plan_batch_calls():
    from repro.core.preemption_jax import persistent_batch_session

    from tests.test_fused_sourcing import random_cluster

    cluster = random_cluster(13)
    s1 = persistent_batch_session(cluster, (WL3["B"], WL3["C"]), 0.5)
    s2 = persistent_batch_session(cluster, (WL3["B"], WL3["C"]), 0.5)
    assert s1 is s2, "clean state + same request classes must reuse"
    # different request mix or alpha -> fresh session
    s3 = persistent_batch_session(cluster, (WL3["C"], WL3["B"]), 0.5)
    assert s3 is not s2
    s4 = persistent_batch_session(cluster, (WL3["C"], WL3["B"]), 0.3)
    assert s4 is not s3


def test_persistent_session_invalidated_by_mutation():
    from repro.core.preemption_jax import persistent_batch_session

    from tests.test_fused_sourcing import random_cluster

    cluster = random_cluster(17)
    s1 = persistent_batch_session(cluster, (WL3["B"], WL3["B"]), 0.5)
    sched = TopoScheduler(cluster, engine="imp_batched")
    sched.plan(WL3["B"], allow_normal=False).commit()   # mutates the cluster
    s2 = persistent_batch_session(cluster, (WL3["B"], WL3["B"]), 0.5)
    assert s2 is not s1, "any invalidate_node must void the cached session"


def test_persistent_session_parity_across_repeated_plan_batch():
    """Repeated identical plan_batch bursts (pure reads, session reused)
    must stay decision-identical to the host engine every round."""
    from tests.test_fused_sourcing import random_cluster

    batch = [WL3["B"], WL3["C"], WL3["B"]]
    host = TopoScheduler(random_cluster(23), engine="imp")
    want = [_decision_key(t.decision) for t in host.plan_batch(batch)]
    sched = TopoScheduler(random_cluster(23), engine="imp_batched")
    for _ in range(3):
        got = [_decision_key(t.decision) for t in sched.plan_batch(batch)]
        assert got == want


def test_persistent_session_parity_across_commit_bursts():
    """Bursts separated by commits: the session rebuilds after each commit
    and the whole sequence matches per-request planning on imp."""
    seqs = {}
    for engine in ("imp", "imp_batched"):
        sched = TopoScheduler(_partial_cluster(8, nodes=4, fill=0.9),
                              engine=engine)
        seq = []
        for _ in range(3):
            txns = sched.plan_batch([WL3["B"], WL3["C"], WL3["B"]])
            for t in txns:
                t.commit()
            seq.extend(_decision_key(t.decision) for t in txns)
        seqs[engine] = seq
    assert seqs["imp"] == seqs["imp_batched"], seqs


# ---------------------------------------------------------------------------------
# _lowest_bits feasibility semantics (race hardening)
# ---------------------------------------------------------------------------------

def test_lowest_bits_returns_none_instead_of_raising():
    assert _lowest_bits(0b101, 2, 8) == 0b101
    assert _lowest_bits(0b101, 3, 8) is None      # was: bare ValueError
    assert _lowest_bits(0, 1, 8) is None
    assert _lowest_bits(0b1111, 2, 8) == 0b11


def test_place_survives_short_masks():
    """place()/place_blind() on raced (inconsistent) masks degrade to None
    rather than crashing the planner."""
    spec = RTX4090_SERVER
    assert place_blind(spec, 0b1, 0b1, 2, 2) is None
    assert place(spec, 0b1, 0b1, 2, 2) is None


# ---------------------------------------------------------------------------------
# Pallas mirror of the placement tier scorer
# ---------------------------------------------------------------------------------

def test_placement_tier_pallas_matches_host_best_tier():
    jnp = pytest.importorskip("jax.numpy")
    from repro.kernels.topo_score import TopoRequest, placement_tier_pallas

    spec = RTX4090_SERVER
    rng = np.random.default_rng(3)
    n = 1200   # > one (8, 128) tile, not a tile multiple
    fg = rng.integers(0, spec.all_gpu_mask + 1, n).astype(np.int32)
    fc = rng.integers(0, spec.all_cg_mask + 1, n).astype(np.int32)
    for ng, nc, cpb, bundle in ((2, 2, 1, True), (4, 4, 1, True),
                                (0, 3, 0, True), (2, 4, 0, False)):
        req = TopoRequest(ng, nc, cpb)
        tier = np.asarray(placement_tier_pallas(
            jnp.asarray(fg), jnp.asarray(fc), spec, req))
        for i in range(0, n, 97):
            assert tier[i] == best_tier(spec, int(fg[i]), int(fc[i]),
                                        ng, nc, bundle), i


def test_blocker_workload_normal_parity_with_degraded_admission():
    """A node whose counts fit but whose topology is infeasible must admit
    DEGRADED via the blind allocator identically on host and device (the
    kubelet best-effort branch of the normal cycle)."""
    v = WorkloadSpec("frag", priority=100, gpus_per_instance=1,
                     cores_per_instance=8, preemptible=True)
    ask = WorkloadSpec("ask", priority=1000, gpus_per_instance=2,
                       cores_per_instance=16, preemptible=False)

    def build():
        from repro.core.placement import Placement

        cluster = Cluster(RTX4090_SERVER, 1)
        # leave GPUs 0 and 4 free (cross-socket), CGs 1..3 and 5..7 busy
        for g in (1, 2, 3, 5, 6, 7):
            cluster.bind(v, 0, Placement(1 << g, 1 << g, 0))
        return cluster

    decs = {}
    for engine in ("imp", "imp_batched"):
        sched = TopoScheduler(build(), engine=engine)
        decs[engine] = _decision_key(
            sched.plan(ask, allow_preempt=False).decision)
    assert decs["imp"] == decs["imp_batched"], decs
    assert decs["imp"][0] == "placed" and not decs["imp"][3]  # a miss
