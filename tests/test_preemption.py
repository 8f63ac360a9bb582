"""IMP (Algorithm 2) correctness: minimality, engine equivalence, scoring."""
import hypothesis.strategies as st
import pytest
from hypothesis import given, settings

from repro.core import preemption
from repro.core.cluster import Cluster
from repro.core.placement import Placement
from repro.core.scoring import Candidate, score, select_best
from repro.core.simulator import SimConfig, build_saturated_cluster
from repro.core.topology import RTX4090_SERVER
from repro.core.workload import WorkloadSpec, table3_workloads

WLS = {w.name: w for w in table3_workloads()}


def random_cluster(seed: int, nodes: int = 4) -> Cluster:
    import random

    rng = random.Random(seed)
    cluster = Cluster(RTX4090_SERVER, nodes)
    d = WLS["D"]
    c = WLS["C"]
    for node in range(nodes):
        free = list(range(8))
        rng.shuffle(free)
        while free:
            if len(free) >= 2 and rng.random() < 0.4:
                g = [free.pop(), free.pop()]
                wl = c
            else:
                g = [free.pop()]
                wl = d
            if rng.random() < 0.2:
                continue  # leave a hole
            mask = sum(1 << x for x in g)
            cluster.bind(wl, node, Placement(mask, mask, 0))
    return cluster


@settings(max_examples=40, deadline=None)
@given(seed=st.integers(0, 10_000), wl_name=st.sampled_from(["A", "B", "C"]))
def test_imp_matches_bruteforce_min_k(seed, wl_name):
    """Algorithm 2 early-stop returns exactly the brute-force minimal size,
    and the same feasible set of candidates at that size."""
    cluster = random_cluster(seed)
    wl = WLS[wl_name]
    for node in range(cluster.num_nodes):
        brute = preemption.brute_force_min_k(cluster, wl, node)
        imp = preemption.flextopo_imp(cluster, wl, node)
        if brute is None:
            assert imp == []
        else:
            k, cands = brute
            assert {c.victims for c in imp} == {c.victims for c in cands}
            assert all(len(c.victims) == k for c in imp)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000), wl_name=st.sampled_from(["A", "B"]))
def test_engines_agree(seed, wl_name):
    """python IMP == pallas engine."""
    from repro.kernels.topo_score import flextopo_imp_pallas

    cluster = random_cluster(seed, nodes=3)
    wl = WLS[wl_name]
    nodes = list(range(cluster.num_nodes))
    key = lambda cs: sorted((c.node, c.victims, c.tier, c.priority_sum)
                            for c in cs)
    py = key(c for n in nodes for c in preemption.flextopo_imp(cluster, wl, n))
    pls = key(c for n in nodes for c in flextopo_imp_pallas(cluster, wl, n))
    assert py == pls


def test_imp_subset_of_exhaustive():
    cluster = random_cluster(123)
    wl = WLS["B"]
    for node in range(cluster.num_nodes):
        imp = {c.victims for c in preemption.flextopo_imp(cluster, wl, node)}
        exh = {c.victims
               for c in preemption.flextopo_exhaustive(cluster, wl, node)}
        assert imp <= exh
        if exh:
            assert min(len(v) for v in exh) == min(len(v) for v in imp)


def test_godel_ignores_topology():
    cluster = random_cluster(7)
    wl = WLS["B"]
    for node in range(cluster.num_nodes):
        c = preemption.godel_standard(cluster, wl, node)
        if c is None:
            continue
        # victims are the lowest-priority ones, greedily
        victims = cluster.victims_on(node, wl.priority)
        chosen = [v for v in victims if v.uid in c.victims]
        others = [v for v in victims if v.uid not in c.victims]
        if chosen and others:
            assert max(v.priority for v in chosen) <= min(
                v.priority for v in others)


def test_eq1_alpha_extremes():
    low_prio_bad_topo = Candidate(0, (1,), tier=2, priority_sum=200)
    high_prio_good_topo = Candidate(0, (2,), tier=0, priority_sum=1000)
    # alpha=1: priority only -> prefers evicting low priority
    assert select_best([low_prio_bad_topo, high_prio_good_topo],
                       alpha=1.0) == low_prio_bad_topo
    # alpha=0: topology only -> prefers NUMA-aligned candidate
    assert select_best([low_prio_bad_topo, high_prio_good_topo],
                       alpha=0.0) == high_prio_good_topo
    assert score(low_prio_bad_topo, 1.0) == pytest.approx(1 / 200)
    assert score(high_prio_good_topo, 0.0) == pytest.approx(1.0)
