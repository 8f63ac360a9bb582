"""§Perf hillclimb (paper-representative cell): candidate-sourcing latency.

The paper's own bottleneck metric (Table 5).  Wall-clock measured on this
host, 100-node saturated cluster, preemptor B (high-p-1000-4-card),
independent preemptions.  Iterations:

  it0  paper-faithful python IMP, naive O(instances) cluster scans
  it1  + per-node instance index & free-mask cache (host-side data structure)
  it4  plan_batch: 8 pending preemptors planned against one snapshot through
       the batched engine (per-request amortized latency)
  it5  fused single dispatch: all subset sizes + on-device Eq. 2 argmax in
       one jit call over incrementally-cached victim rows (imp_batched)

Independent samples are rollback-free: each is a pure ``plan()`` read
against the saturated state — no mutate-then-undo.  Each iteration records
P50/P90 sourcing latency + end-to-end plan() latency.
"""
from __future__ import annotations

import time

import numpy as np

from repro.core.scheduler import TopoScheduler
from repro.core.simulator import SimConfig
from repro.core.workload import table3_workloads

from .common import FULL, emit


def _saturated(nodes: int, node_index: bool = True, seed: int = 11):
    """The shared measurement fixture: one saturated Table 3 cluster."""
    import random

    import repro.core.simulator as sim
    from repro.core.cluster import Cluster

    cfg = SimConfig(num_nodes=nodes, seed=seed)
    cluster = Cluster(cfg.spec, cfg.num_nodes, node_index=node_index)
    sim.saturate(cluster, table3_workloads(),
                 {k: round(v * nodes / 100) for k, v in
                  sim.TABLE3_INITIAL_INSTANCES.items()},
                 random.Random(cfg.seed))
    return cluster


def _measure(engine: str, node_index: bool, nodes: int = 100,
             samples: int = 30, preemptor: str = "B") -> dict:
    wls = {w.name: w for w in table3_workloads()}
    cluster = _saturated(nodes, node_index=node_index)
    sched = TopoScheduler(cluster, engine=engine)
    sourcing, total = [], []
    # warm up jit caches so compile time isn't counted as scheduling latency
    sched.plan(wls[preemptor])
    for _ in range(samples):
        t0 = time.perf_counter()
        dec = sched.plan(wls[preemptor]).decision   # rollback-free read
        total.append((time.perf_counter() - t0) * 1e6)
        if dec.rejected:
            break
        if dec.preempted:
            sourcing.append(dec.sourcing_us)
    return {
        "engine": engine, "node_index": node_index,
        "sourcing_p50": float(np.percentile(sourcing, 50)) if sourcing else 0,
        "sourcing_p90": float(np.percentile(sourcing, 90)) if sourcing else 0,
        "total_p50": float(np.percentile(total, 50)),
        "total_p90": float(np.percentile(total, 90)),
        "n": len(sourcing),
    }


def _measure_plan_batch(engine: str, nodes: int = 100, batch: int = 8,
                        rounds: int = 4, preemptor: str = "B") -> dict:
    """it4: amortized per-request planning latency of one batched plan.

    Reports END-TO-END plan time per request (total_*); the sourcing_*
    fields stay zero because a batched plan interleaves filtering,
    sourcing, and selection per request — a per-phase split would not be
    comparable with the single-plan iterations' sourcing numbers.
    """
    wls = {w.name: w for w in table3_workloads()}
    cluster = _saturated(nodes)
    sched = TopoScheduler(cluster, engine=engine)
    sched.plan_batch([wls[preemptor]] * batch)      # jit warm-up
    per_req = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        txns = sched.plan_batch([wls[preemptor]] * batch)
        per_req.append((time.perf_counter() - t0) * 1e6 / batch)
        assert all(t.decision for t in txns)
    return {
        "engine": engine, "node_index": True,
        "sourcing_p50": 0.0,
        "sourcing_p90": 0.0,
        "total_p50": float(np.percentile(per_req, 50)),
        "total_p90": float(np.percentile(per_req, 90)),
        "n": len(per_req) * batch,
    }


ITERATIONS = [
    ("it0_python_imp_naive", "imp", False),
    ("it1_python_imp_indexed", "imp", True),
    ("it5_fused_single_dispatch", "imp_batched", True),
]


def run(full: bool = FULL) -> list[dict]:
    nodes = 100
    samples = 50 if full else 25
    rows = []
    for name, engine, idx in ITERATIONS:
        r = _measure(engine, idx, nodes=nodes, samples=samples)
        r["iteration"] = name
        rows.append(r)
        emit(f"perf_sched_{name}", r["sourcing_p50"],
             f"sourcing_p90={r['sourcing_p90']:.0f}us "
             f"total_p50={r['total_p50']:.0f}us "
             f"total_p90={r['total_p90']:.0f}us n={r['n']}")
    r = _measure_plan_batch("imp_batched", nodes=nodes,
                            batch=8 if full else 4)
    r["iteration"] = "it4_plan_batch"
    rows.append(r)
    emit("perf_sched_it4_plan_batch", r["total_p50"],
         f"end_to_end_per_request_p90={r['total_p90']:.0f}us n={r['n']}")
    return rows


if __name__ == "__main__":
    run()
