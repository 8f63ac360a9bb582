"""The paper's Fig. 3 walkthrough: three 4090 servers, workloads A/B/C
co-located at saturation, then a topology-aware scale-up of A.

Shows the exact failure mode of priority-only preemption (victims freed on
the wrong socket) and how FlexTopo+IMP fixes it — plus the transactional
scheduler API:

* ``sched.plan(wl)`` evaluates Filtering → Sorting → Bind against a
  copy-on-write view and returns a ``Transaction``; the cluster is untouched
  until ``txn.commit()``, and ``txn.rollback()`` restores the exact prior
  state (original victim uids and placements) after a commit.
* ``sched.plan_batch([wl, ...])`` plans several scale-ups against ONE
  snapshot so the decisions compose before anything is committed.
* ``@register_engine("name")`` plugs a custom victim-sourcing engine into
  the registry, making it a valid ``TopoScheduler(engine="name")`` choice.

  PYTHONPATH=src python examples/preemption_demo.py
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent / "src"))

from repro.core import (Cluster, RTX4090_SERVER, TopoScheduler,
                        register_engine, registered_engines, table1_workloads)
from repro.core.preemption import flextopo_imp
from repro.jax_cache import enable_compile_cache


def gpu_map(cluster, node):
    topo = cluster.topos[node]
    cells = []
    for g in range(8):
        owner = topo.graph.nodes[("gpu", g)]["used_by"]
        cells.append((owner or "....")[:6].ljust(6))
    return " ".join(cells[:4]) + " | " + " ".join(cells[4:])


def show(cluster, title):
    print(f"\n== {title} ==")
    print("          socket 0                    | socket 1")
    for n in range(cluster.num_nodes):
        print(f"machine {n + 1}: {gpu_map(cluster, n)}")


def saturated(engine):
    wls = {w.name: w for w in table1_workloads()}
    cluster = Cluster(RTX4090_SERVER, 3)
    sched = TopoScheduler(cluster, engine=engine)
    sched.schedule(wls["A"])
    for _ in range(6):
        sched.schedule(wls["B"])
    for _ in range(8):
        sched.schedule(wls["C"])
    return cluster, sched, wls


# A custom engine is one decorated sourcing function: here, plain IMP
# restricted to even node INDICES — machines 1 and 3, say a maintenance
# policy that fences off the rest.
@register_engine("imp_even_nodes")
def imp_even_nodes(cluster, workload, node):
    return flextopo_imp(cluster, workload, node) if node % 2 == 0 else []


def main() -> None:
    enable_compile_cache()
    for engine in ("godel", "imp"):
        cluster, sched, wls = saturated(engine)
        show(cluster, f"saturated cluster (engine={engine})")

        # two-phase: plan (pure read) ... then commit
        txn = sched.plan(wls["A"])
        dec = txn.decision
        print(f"\nscale-up A with engine={engine}: planned "
              f"{dec.kind} on machine {dec.node + 1}, victims={dec.victims}")
        txn.commit()
        print(f"  committed: evicted {[v.name for v in dec.evicted]}")
        print(f"  placement tier={dec.placement.tier} "
              f"({['NUMA', 'socket', 'cross-socket'][dec.placement.tier]}) "
              f"topology hit={dec.hit}")
        show(cluster, f"after preemption (engine={engine})")

        # rollback restores the exact pre-commit state (same victim uids)
        txn.rollback()
        show(cluster, f"after rollback (engine={engine})")
        print("-" * 70)

    # batched admission: plan 3 scale-ups against one snapshot, commit together
    cluster, sched, wls = saturated("imp")
    txns = sched.plan_batch([wls["B"], wls["B"], wls["A"]])
    print("\nplan_batch against one snapshot:",
          [(t.decision.kind, t.decision.node + 1) for t in txns])
    for t in txns:
        t.commit()
    show(cluster, "after committing the batch")

    # the registry knows every engine, including custom ones
    print("\nregistered engines:", ", ".join(registered_engines()))
    cluster, sched, wls = saturated("imp_even_nodes")
    dec = sched.preempt(wls["B"])
    print(f"custom engine chose machine {dec.node + 1} "
          f"(even node indices only), hit={dec.hit}")


if __name__ == "__main__":
    main()
