"""Replay a run's log against the plain reference and count what disagrees.

The reference keeps its own fleet: the start allocation the benchmark made,
then every instance the run bound or evicted, in order, with uids given out by
its own counter.  At each plan chosen for checking it works out Algorithm 1's
decision on the state that plan saw (the committed fleet, plus the planned
decisions made before it on the same copy-on-write view) and compares the
decision keys.  Every commit is checked for having applied exactly its
decision: the victims evicted, then the preemptor bound on the decided node
with the decided masks.
"""
from __future__ import annotations

from bench.reference import Fleet, Reference, apply


def replay(log: list[tuple], topo, reqs: dict, alpha: float, num_nodes: int,
           start: list[tuple], checked: set[int]) -> dict:
    """Returns counts: ``plans_checked``, ``plans_wrong``, ``commits_wrong``,
    ``ops_wrong`` and up to five ``examples`` of disagreements."""
    ref = Reference(topo, alpha)
    fleet = Fleet(topo, num_nodes)
    for name, node, gm, cm in start:
        fleet.bind(reqs[name], node, gm, cm)
    out = {"plans_checked": 0, "plans_wrong": 0, "commits_wrong": 0,
           "ops_wrong": 0, "examples": []}
    batch: list[tuple] = []
    ops: list[tuple] = []

    def wrong(what: str, counter: str, detail) -> None:
        out[counter] += 1
        if len(out["examples"]) < 5:
            out["examples"].append(f"{what}: {detail}")

    for i, rec in enumerate(log):
        kind = rec[0]
        if kind == "bind":
            _, uid, node, gm, cm, name = rec
            ops.append(("bind", node, gm, cm, name))
            try:
                got = fleet.bind(reqs[name], node, gm, cm)
            except ValueError as e:
                wrong(f"record {i}", "ops_wrong", e)
                continue
            if got != uid:
                wrong(f"record {i}", "ops_wrong",
                      f"bound uid {uid}, the reference counts {got}")
        elif kind == "evict":
            ops.append(("evict", rec[1]))
            if rec[1] not in fleet.where:
                wrong(f"record {i}", "ops_wrong",
                      f"evicted uid {rec[1]} is not live")
                continue
            fleet.evict(rec[1])
        elif kind == "plan":
            _, same_view, name, allow_normal, allow_preempt, key = rec
            r = reqs[name]
            if not same_view:
                batch = []
            if i in checked:
                base = fleet
                if batch:
                    base = fleet.copy()
                    for br, bkey in batch:
                        apply(base, br, bkey)
                want = ref.decide(base, r, allow_normal, allow_preempt)
                out["plans_checked"] += 1
                if want != key:
                    wrong(f"plan record {i} ({name})", "plans_wrong",
                          f"program {key} reference {want}")
            batch.append((r, key))
            ops = []
        elif kind == "commit":
            _, name, key = rec
            _, node, victims, _, masks = key
            want = [("evict", u) for u in victims] + [
                ("bind", node, masks[0], masks[1], name)]
            if ops != want:
                wrong(f"commit record {i}", "commits_wrong",
                      f"applied {ops} for decision {key}")
            ops = []
    return out
