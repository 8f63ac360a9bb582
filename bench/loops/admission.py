"""The ``admission`` loop: a closed loop with one client and a backlog that is
never empty, as a serial scheduler drains pending scale-ups one pod at a time.

Requests are drawn in decks (each deck holds every class in the mix's integer
``request_weights``, shuffled by the seed), so every seed asks for the same
mix.  Every request is ``TopoScheduler.plan`` then ``Transaction.commit``.
After each committed request of a ``release`` class, one seeded-random other
live instance of the same class is released with ``Cluster.evict`` (a
scale-down elsewhere), which keeps the fleet's class counts stationary.  With
``requeue``, every evicted victim re-enters a FIFO queue that is planned, one
request each, before the next drawn request.

The start state is the configuration's saturation allocation at the mix's
``fill``: the same set of nodes for every seed, in a seeded order
(`bench.fleet.start_state`).  Set-up runs at least ``warmup_decisions``
decisions, and goes on until ``warmup_quiet_decisions`` decisions in a row have
built no program (at most ``warmup_max_decisions`` in all): the device state's
pending-row overlay and its flush reach their largest buckets only after some
hundreds of decisions, at a point that depends on the seed.  After the window, a
seeded sample of ``check_plans`` window plans is checked against the plain
reference.
"""
from __future__ import annotations

import math
import random
import time
import types
from collections import deque
from functools import reduce

from bench import check, fleet
from bench.measure import Window, annotator, device_info, quiet
from bench.program import Recorder, program_spec, scheduler_kw

#: the host spans the trace reduction attributes idle device time to
SPANS = ("plan", "commit", "evict", "generate")


def _deck(weights: dict[str, int]) -> list[str]:
    g = reduce(math.gcd, weights.values())
    return [name for name, w in weights.items() for _ in range(w // g)]


class _Live:
    """Live uids of one class, for O(1) seeded-random release."""

    def __init__(self) -> None:
        self.uids: list[int] = []
        self.at: dict[int, int] = {}

    def add(self, uid: int) -> None:
        self.at[uid] = len(self.uids)
        self.uids.append(uid)

    def remove(self, uid: int) -> None:
        i = self.at.pop(uid)
        last = self.uids.pop()
        if last != uid:
            self.uids[i] = last
            self.at[last] = i


class Admission:
    """Drives the loop over one fleet."""

    def __init__(self, cfg: dict, mix: dict, seed: int, start: list[tuple],
                 engine_overrides: dict | None = None) -> None:
        from repro.core import Cluster, Placement, TopoScheduler

        spec, self.wls = program_spec(cfg)
        self.cluster = Cluster(spec, cfg["num_nodes"])
        self.live = {name: _Live() for name in self.wls}
        for uid, (name, node, gm, cm) in enumerate(start):
            inst = self.cluster.bind(self.wls[name], node,
                                     Placement(gpu_mask=gm, cg_mask=cm,
                                               tier=0))
            if inst.uid != uid:
                raise RuntimeError("start allocation bound out of order")
            self.live[name].add(uid)
        self.sched = TopoScheduler(self.cluster, warmup=True,
                                   **scheduler_kw(cfg, engine_overrides))
        self.rec = Recorder()
        self.cluster.add_inst_listener(self.rec.on_inst)
        self.release = set(mix["release"])
        self.requeue = mix["requeue"]
        self.deck = _deck(mix["request_weights"])
        self.rng = random.Random(seed)
        self.drawn: deque[str] = deque()
        self.pending: deque[str] = deque()
        #: per decision: (source, kind, plan seconds, commit seconds,
        #: index of its plan record)
        self.decisions: list[tuple] = []
        #: seconds spent in `Cluster.evict` (the releases)
        self.evict_s = 0.0

    def _next(self) -> tuple[str, str]:
        if self.requeue and self.pending:
            return "requeue", self.pending.popleft()
        if not self.drawn:
            deck = list(self.deck)
            self.rng.shuffle(deck)
            self.drawn.extend(deck)
        return "request", self.drawn.popleft()

    def step(self, annotate) -> None:
        """One decision: plan, commit, and the release that follows it."""
        with annotate("generate"):
            source, name = self._next()
            wl = self.wls[name]
        t0 = time.perf_counter()
        with annotate("plan"):
            txn = self.sched.plan(wl)
        t1 = time.perf_counter()
        idx = self.rec.plan(False, wl, True, True, txn)
        t2 = time.perf_counter()
        with annotate("commit"):
            dec = txn.commit()
        t3 = time.perf_counter()
        self.rec.commit(dec)
        # plan and commit times, the recording between them left out
        self.decisions.append((source, dec.kind, t1 - t0, t3 - t2, idx))
        if dec.rejected:
            return
        self.live[name].add(dec.instance.uid)
        for v in dec.evicted:
            self.live[v.workload.name].remove(v.uid)
            self.pending.append(v.workload.name)
        if (source == "request" and name in self.release
                and len(self.live[name].uids) > 1):
            with annotate("generate"):
                pool = self.live[name].uids
                uid = dec.instance.uid
                while uid == dec.instance.uid:
                    uid = pool[self.rng.randrange(len(pool))]
                self.live[name].remove(uid)
            t4 = time.perf_counter()
            with annotate("evict"):
                self.cluster.evict(uid)
            self.evict_s += time.perf_counter() - t4

    def device_state(self):
        return self.cluster.device_state(
            sharded=self.sched.engine == "imp_sharded")


def _sample(seed: int, population: list[int], size: int) -> set[int]:
    rng = random.Random(f"check:{seed}")
    return set(rng.sample(population, min(size, len(population))))


def run(cfg, mix, seed, seconds, traced, engine_overrides, watch, t_start):
    """Set-up, the window and the check; returns ``(run, got, attempted,
    device)`` for `bench.run.run_cell`."""
    start = fleet.start_state(cfg, seed, mix["fill"])
    drv = Admission(cfg, mix, seed, start, engine_overrides)
    built, last_built = watch.mark(), 0
    for n in range(1, mix["warmup_max_decisions"] + 1):
        drv.step(quiet)
        if watch.mark() != built:
            built, last_built = watch.mark(), n
        if (n >= mix["warmup_decisions"]
                and n - last_built >= mix["warmup_quiet_decisions"]):
            break
    warmed = len(drv.decisions)
    setup_s = time.perf_counter() - t_start
    annotate = annotator(traced)
    n0 = len(drv.decisions)
    fb0 = dict(drv.device_state().fallbacks)
    mark = watch.mark()
    evict0 = drv.evict_s
    errors = []
    with Window(traced, SPANS) as win:
        while time.perf_counter() - win.t0 < seconds:
            try:
                drv.step(annotate)
            except Exception as e:  # a failed admission, counted as such
                errors.append(f"{type(e).__name__}: {e}")
    compiles = watch.delta(mark)
    fb = {k: v - fb0.get(k, 0) for k, v in drv.device_state().fallbacks.items()}
    device = device_info(cfg["chips"])
    decisions = drv.decisions[n0:]
    evict_s = drv.evict_s - evict0
    log = drv.rec.log
    del drv                                  # the program's state goes
    checked = _sample(seed, [d[4] for d in decisions], mix["check_plans"])
    got = check.replay(log, fleet.topology(cfg), fleet.requests(cfg),
                       cfg["alpha"], cfg["num_nodes"], start, checked)
    got["errors"] = len(errors)
    got["examples"] = errors[:3] + got["examples"]
    kinds = {k: sum(d[1] == k for d in decisions)
             for k in ("placed", "preempted", "rejected")}
    got["summary"] = (f"window: {len(decisions)} decisions after {warmed} "
                      "warm-up ones, " + ", ".join(
        f"{k} {100.0 * v / max(1, len(decisions)):.1f}%"
        for k, v in kinds.items())
        + f"; {compiles} programs built in the window; of its "
        f"{win.seconds:.3f} s, plan {sum(d[2] for d in decisions):.3f} s, "
        f"commit {sum(d[3] for d in decisions):.3f} s, evict {evict_s:.3f} s")
    out = types.SimpleNamespace(
        setup_s=setup_s, window_s=win.seconds, decisions=decisions,
        compiles=compiles, fallbacks=fb, trace=win.trace)
    return out, got, len(decisions), device
