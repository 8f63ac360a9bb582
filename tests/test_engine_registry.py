"""Engine registry: resolution, clear errors, and cross-engine agreement."""
import random

import pytest

from repro.core import (Cluster, RTX4090_SERVER, TopoScheduler,
                        UnknownEngineError, get_engine, register_engine,
                        registered_engines, table3_workloads)
from repro.core.placement import Placement

WL3 = {w.name: w for w in table3_workloads()}


def small_cluster(seed: int = 0, nodes: int = 4) -> Cluster:
    """4-node cluster of C/D instances with holes — preemption territory."""
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


def test_unknown_engine_raises_listing_registered():
    with pytest.raises(UnknownEngineError) as exc:
        get_engine("definitely_not_an_engine")
    msg = str(exc.value)
    for name in ("godel", "imp", "imp_batched", "imp_pallas"):
        assert name in msg
    # also a ValueError, so legacy except-clauses still catch it
    assert isinstance(exc.value, ValueError)


def test_scheduler_rejects_unknown_engine_at_construction():
    cluster = Cluster(RTX4090_SERVER, 1)
    with pytest.raises(UnknownEngineError):
        TopoScheduler(cluster, engine="tpyo")


def test_registry_contains_all_paper_engines():
    names = registered_engines()
    for name in ("godel", "exhaustive", "imp", "imp_batched", "imp_pallas"):
        assert name in names


def test_scheduler_docstring_derives_from_registry():
    """Satellite: the documented engine list can no longer drift."""
    import repro.core.scheduler as sched_mod

    for name in registered_engines():
        assert name in sched_mod.__doc__


def test_custom_engine_registration_roundtrip():
    from repro.core.preemption import flextopo_imp

    @register_engine("registry_test_engine")
    def my_engine(cluster, workload, node):
        return flextopo_imp(cluster, workload, node)

    try:
        assert "registry_test_engine" in registered_engines()
        cluster = small_cluster(3)
        sched = TopoScheduler(cluster, engine="registry_test_engine")
        ref = TopoScheduler(cluster, engine="imp")
        dec = sched.plan(WL3["B"], allow_normal=False).decision
        refdec = ref.plan(WL3["B"], allow_normal=False).decision
        assert (dec.kind, dec.node, dec.victims) == \
            (refdec.kind, refdec.node, refdec.victims)
    finally:
        from repro.core import engines as engines_mod

        engines_mod._REGISTRY.pop("registry_test_engine", None)


@pytest.mark.parametrize("seed", [0, 7, 42])
@pytest.mark.parametrize("wl_name", ["A", "B", "C"])
def test_all_engines_agree_on_feasibility(seed, wl_name):
    """Hit/miss decisions may differ across engines (the baseline is
    topology-blind); bind FEASIBILITY may not: either every engine finds a
    valid plan for the preemptor or none does, and every committed placement
    must actually fit the freed resources (commit validates)."""
    wl = WL3[wl_name]
    kinds = {}
    for engine in registered_engines():
        cluster = small_cluster(seed)
        sched = TopoScheduler(cluster, engine=engine)
        txn = sched.plan(wl)
        kinds[engine] = txn.decision.rejected
        dec = txn.commit()      # raises TransactionError on an invalid bind
        if not dec.rejected:
            assert dec.instance.uid in cluster.instances
            for v in dec.evicted:
                assert v.uid not in cluster.instances
    assert len(set(kinds.values())) == 1, f"feasibility disagreement: {kinds}"
