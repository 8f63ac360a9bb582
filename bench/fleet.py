"""Fleets: a configuration file's server SKU, workload classes and start state.

A configuration (``bench/configs/<name>.json``) states the SKU's topology, the
workload classes (paper Table 3), the node count and the start allocation.  The
start allocation is generated here from the seed, by the paper's §5 protocol:
the Table 3 per-100-node instance counts placed largest-first with each GPU
paired to a CoreGroup of its own NUMA node but the NUMA nodes picked at random
(the fragmented "before" state of Fig. 8), on a base block of ``tile_nodes``
nodes that is repeated across the fleet.  It is data, made by the benchmark: the
system under test and the plain reference are both handed the same list.

A run's start state (`start_state`) is the configuration's own block, drawn from
its ``start.seed`` and so the same for every run, with the fleet's node ids
shuffled by the run's seed: every seed starts from the same set of nodes, in
another order.  The block decides how much work a decision costs (how many
victims a preemption takes, how many requeues follow), so a block drawn from the
run's seed would change the work from seed to seed.
"""
from __future__ import annotations

import json
import random
from pathlib import Path

from bench.reference import Request, Topology

ROOT = Path(__file__).resolve().parent


def load_config(name: str) -> dict:
    with open(ROOT / "configs" / f"{name}.json") as f:
        return json.load(f)


def topology(cfg: dict) -> Topology:
    s = cfg["server"]
    return Topology(num_sockets=s["num_sockets"], num_numa=s["num_numa"],
                    num_cores=s["num_cores"], num_gpus=s["num_gpus"],
                    coregroup_size=s["coregroup_size"])


def requests(cfg: dict) -> dict[str, Request]:
    size = cfg["server"]["coregroup_size"]
    return {w["name"]: Request(
        name=w["name"], priority=w["priority"], gpus=w["gpus"],
        cgs=w["cores"] // size, bundle=w["numa_policy"] == "guaranteed",
        preemptible=w["preemptible"]) for w in cfg["workloads"]}


def _random_bits(rng: random.Random, mask: int, k: int, n: int) -> int:
    out = 0
    for i in rng.sample([i for i in range(n) if mask >> i & 1], k):
        out |= 1 << i
    return out


def _aligned(t: Topology, fg: int, fc: int, r: Request, rng: random.Random):
    """Each GPU with CoreGroups of its own NUMA node, NUMA order shuffled half
    of the time (replicas deployed in bursts pack sequentially)."""
    per_bundle = r.cgs // r.gpus if r.gpus else 0
    gm = cm = 0
    numas = list(range(t.num_numa))
    if rng.random() >= 0.5:
        rng.shuffle(numas)
    left = r.gpus
    for u in numas * t.gpus_per_numa:
        if left == 0:
            break
        ug = fg & t.numa_gpu[u] & ~gm
        uc = fc & t.numa_cg[u] & ~cm
        if ug and uc.bit_count() >= per_bundle:
            gm |= ug & -ug
            taken = 0
            for c in range(t.num_coregroups):
                if taken == per_bundle:
                    break
                if uc >> c & 1:
                    cm |= 1 << c
                    taken += 1
            left -= 1
    if left:
        return None
    extra = r.cgs - cm.bit_count()
    if extra:
        avail = fc & ~cm
        if avail.bit_count() < extra:
            return None
        cm |= _random_bits(rng, avail, extra, t.num_coregroups)
    return gm, cm


def _saturate(t: Topology, reqs: dict[str, Request], counts: dict[str, int],
              nodes: int, rng: random.Random) -> list[tuple]:
    full = ((1 << t.num_gpus) - 1, (1 << t.num_coregroups) - 1)
    free = [full] * nodes
    out = []
    for r in sorted(reqs.values(), key=lambda r: -r.gpus):
        for _ in range(counts.get(r.name, 0)):
            feasible = [n for n in range(nodes)
                        if free[n][0].bit_count() >= r.gpus
                        and free[n][1].bit_count() >= r.cgs]
            if not feasible:
                raise RuntimeError(f"start allocation: no node fits {r.name}")
            masks = None
            for node in rng.sample(feasible, len(feasible)):
                masks = _aligned(t, *free[node], r, rng)
                if masks is not None:
                    break
            if masks is None:
                node = rng.choice(feasible)
                fg, fc = free[node]
                masks = (_random_bits(rng, fg, r.gpus, t.num_gpus),
                         _random_bits(rng, fc, r.cgs, t.num_coregroups))
            fg, fc = free[node]
            free[node] = (fg & ~masks[0], fc & ~masks[1])
            out.append((r.name, node, masks[0], masks[1]))
    return out


def start_allocation(cfg: dict, seed: int, fill: float) -> list[tuple]:
    """``(class, node, gpu_mask, cg_mask)`` per instance, in bind order.

    ``fill`` 1.0 saturates every GPU (trimming the lowest priorities where
    rounding oversubscribes the block); below 1.0 the counts are scaled by it.
    """
    t = topology(cfg)
    reqs = requests(cfg)
    nodes = cfg["num_nodes"]
    base = min(nodes, cfg["start"]["tile_nodes"])
    per100 = cfg["start"]["instances_per_100_nodes"]
    scale = base / 100.0 * (1.0 if fill >= 1.0 else fill)
    counts = {k: max(0, round(v * scale)) for k, v in per100.items()}
    if fill >= 1.0:
        capacity = base * t.num_gpus
        order = sorted(reqs.values(), key=lambda r: r.priority)
        while sum(counts[k] * reqs[k].gpus for k in counts) > capacity:
            for r in order:
                if counts.get(r.name, 0) > 0:
                    counts[r.name] -= 1
                    break
    block = _saturate(t, reqs, counts, base, random.Random(seed))
    return [(name, node + blk * base, gm, cm)
            for blk in range(nodes // base)
            for name, node, gm, cm in block]


def start_state(cfg: dict, seed: int, fill: float) -> list[tuple]:
    """A run's start allocation: the configuration's block (``start.seed``),
    node ids shuffled by ``seed``; the same instances in the same bind order."""
    perm = list(range(cfg["num_nodes"]))
    random.Random(f"nodes:{seed}").shuffle(perm)
    return [(name, perm[node], gm, cm) for name, node, gm, cm
            in start_allocation(cfg, cfg["start"]["seed"], fill)]
