"""The benchmark's own yardstick on the CPU: its start allocation is the
program's Table 3 saturation protocol, and its plain reference makes the host
``imp`` engine's decisions."""
import random

import pytest

from bench import fleet
from bench.program import decision_key, program_spec
from bench.reference import Fleet, Reference, apply

CFG = dict(fleet.load_config("rtx4090.10240"), num_nodes=128)


@pytest.mark.parametrize("nodes,fill", [(128, 1.0), (256, 0.6)])
def test_start_allocation_is_the_saturation_protocol(nodes, fill):
    from repro.core.simulator import build_scaled_cluster

    cfg = dict(CFG, num_nodes=nodes)
    got = fleet.start_allocation(cfg, 7, fill)
    cluster = build_scaled_cluster(nodes, seed=7, fill=fill)
    want = [(i.workload.name, i.node, i.gpu_mask, i.cg_mask)
            for i in cluster.instances.values()]
    assert got == want


@pytest.mark.parametrize("fill", [1.0, 0.6])
def test_every_seed_starts_from_the_same_nodes_in_another_order(fill):
    cfg = dict(CFG, num_nodes=256)
    a, b = (fleet.start_state(cfg, seed, fill) for seed in (5, 2**31 + 9))

    def nodes(start):
        out = {}
        for name, node, gm, cm in start:
            out.setdefault(node, []).append((name, gm, cm))
        return out

    assert [r[0] for r in a] == [r[0] for r in b]
    assert nodes(a) != nodes(b)
    assert sorted(nodes(a).values()) == sorted(nodes(b).values())
    block = fleet.start_allocation(cfg, cfg["start"]["seed"], fill)
    assert sorted(nodes(a).values()) == sorted(nodes(block).values())


def test_reference_matches_host_imp_on_a_seeded_stream():
    """Mixed plans at 128 nodes, committed in turn: the reference and
    ``TopoScheduler(engine="imp")`` give identical decision keys."""
    from repro.core import Cluster, Placement, TopoScheduler

    start = fleet.start_allocation(CFG, 3, 0.9)
    spec, wls = program_spec(CFG)
    cluster = Cluster(spec, CFG["num_nodes"])
    ref_fleet = Fleet(fleet.topology(CFG), CFG["num_nodes"])
    reqs = fleet.requests(CFG)
    for name, node, gm, cm in start:
        cluster.bind(wls[name], node, Placement(gm, cm, 0))
        ref_fleet.bind(reqs[name], node, gm, cm)
    sched = TopoScheduler(cluster, engine="imp", alpha=CFG["alpha"])
    ref = Reference(fleet.topology(CFG), CFG["alpha"])
    rng = random.Random(11)
    kinds = set()
    for _ in range(40):
        name = rng.choice("AABBCD")
        want = ref.decide(ref_fleet, reqs[name])
        dec = sched.plan(wls[name]).commit()
        assert decision_key(dec) == want
        kinds.add(dec.kind)
        uid = apply(ref_fleet, reqs[name], want)
        assert uid == (None if dec.rejected else dec.instance.uid)
    assert {"placed", "preempted"} <= kinds


def test_apply_refuses_a_victim_of_higher_priority():
    t = fleet.topology(CFG)
    reqs = fleet.requests(CFG)
    f = Fleet(t, 1)
    a = f.bind(reqs["A"], 0, 255, 255)
    with pytest.raises(ValueError):
        apply(f, reqs["B"], ("preempted", 0, (a,), True, (15, 15, 1)))
