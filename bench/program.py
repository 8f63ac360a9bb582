"""The benchmark's bridge to the system under test, shared by every loop.

A configuration's SKU and workload classes become the program's `ServerSpec`
and `WorkloadSpec`s here, and `Recorder` logs, for the plain reference, every
plan with its decision key, every commit, and every instance bound or evicted,
in order.
"""
from __future__ import annotations


def decision_key(dec) -> tuple:
    """(kind, node, victims, hit, (gpu_mask, cg_mask, tier) or None)."""
    p = dec.placement
    return (dec.kind, dec.node, tuple(dec.victims), dec.hit,
            None if p is None else (p.gpu_mask, p.cg_mask, p.tier))


class Recorder:
    """In-order log of plans, commits and instance ops on one cluster."""

    def __init__(self) -> None:
        self.log: list[tuple] = []
        self.last_view = None

    def on_inst(self, delta: int, inst) -> None:
        if delta > 0:
            self.log.append(("bind", inst.uid, inst.node, inst.gpu_mask,
                             inst.cg_mask, inst.workload.name))
        else:
            self.log.append(("evict", inst.uid))

    def plan(self, same_view: bool, workload, allow_normal: bool,
             allow_preempt: bool, txn) -> int:
        self.log.append(("plan", same_view, workload.name, allow_normal,
                         allow_preempt, decision_key(txn.decision)))
        return len(self.log) - 1

    def commit(self, dec) -> None:
        if not dec.rejected:
            self.log.append(("commit", dec.workload.name, decision_key(dec)))


def program_spec(cfg: dict):
    """The program's `ServerSpec` and `WorkloadSpec`s from a configuration."""
    from repro.core import ServerSpec, TopoPolicy, WorkloadSpec

    s = cfg["server"]
    spec = ServerSpec(name=s["name"], num_sockets=s["num_sockets"],
                      num_numa=s["num_numa"], num_cores=s["num_cores"],
                      num_gpus=s["num_gpus"],
                      coregroup_size=s["coregroup_size"])
    wls = {w["name"]: WorkloadSpec(
        w["name"], priority=w["priority"], gpus_per_instance=w["gpus"],
        cores_per_instance=w["cores"], preemptible=w["preemptible"],
        numa_policy=TopoPolicy(w["numa_policy"]),
        socket_policy=TopoPolicy(w["socket_policy"]),
        critical=w["critical"], kind=w["kind"]) for w in cfg["workloads"]}
    return spec, wls


def scheduler_kw(cfg: dict, overrides: dict | None) -> dict:
    """The configuration's scheduler settings, with ``overrides`` applied
    (the control runs the program with other settings)."""
    kw = dict(engine=cfg["engine"], alpha=cfg["alpha"],
              shortlist_k=cfg["shortlist_k"],
              shortlist_mode=cfg["shortlist_mode"])
    kw.update(overrides or {})
    return kw
