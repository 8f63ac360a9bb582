"""Topology-aware scheduler (paper §3.1, Algorithm 1) — transactional API.

Pipeline per scheduling attempt:

1. **Normal cycle** — place the instance on a node with free resources,
   topology-aware (tier-minimizing) for FlexTopo modes, lowest-index blind for
   the baseline mode.
2. **Preemption** (only if the normal cycle fails):
   * *Guaranteed Filtering* — keep candidate nodes that could satisfy the
     preemptor's topology policy if ALL their victims were drained.
   * *Best-effort Sorting* — source victim-set candidates with the
     configured engine ({engines}), then select the global argmax of
     Eq. 1/Eq. 2.
   * *Bind* — evict the victims and place the preemptor.

For host engines, the normal cycle and Filtering are python loops over the
nodes and Sorting is sourced per node.  For engines registered with
``fused_place=True`` (``imp_batched``, the default fast path) the scheduler
does NO per-node host work at all: the ENTIRE Algorithm 1 — normal-cycle
argmin, Guaranteed Filtering, Sorting, Eq. 2 selection, and the §3.4
placement mask construction (`repro.core.placement_jax`) — runs as ONE jit
dispatch over the cluster's device-resident state (`Cluster.device_state`).
The fully-drained masks are popcounted on device, copy-on-write view deltas
are overlaid in-dispatch, the preemptive subset sweep executes only when
the normal cycle finds nothing (``lax.cond``), and the winner comes back as
a handful of int32s CARRYING ITS CONCRETE GPU/CoreGroup masks — the host
never re-runs ``place()`` on the winning node.  ``fused_filter`` engines
without ``fused_place`` keep the host normal cycle but fuse Filtering into
sourcing (``nodes=None``).  ``invalidate_node`` (hit by every
bind/evict/restore) marks single device rows stale; they re-upload as one
``.at[rows].set()`` scatter on the next plan, so cluster state never leaves
the accelerator wholesale.  Per-plan host work is O(delta), not O(N): the
mutation op journal replays dirty mirror rows vectorized, and view-delta
patch rows are rebuilt ON DEVICE by the delta encoder
(`repro.core.cluster.ViewDelta`) instead of host-encoded per row.

``imp_sharded`` (`repro.core.cluster_parallel`) is ``imp_batched`` with
the device-resident state sharded over a 1-D device mesh
(``Cluster.device_state(sharded=True)``: node axis padded to the mesh
size, `NamedSharding` pinned through scatter/rebuild/delta-encode): the
same fused entry points build each program for the state's mesh, per-node
math stays shard-local, only the final argmax chain crosses shards, and
decisions stay bit-identical — plans, batch sessions, and the day cycle
work unchanged at thousands of nodes.

The engine list above is rendered from the live registry
(``repro.core.engines.registered_engines``); custom engines registered with
``@register_engine("name")`` become valid ``engine=`` arguments automatically.
Pass ``warmup=True`` to pre-compile the engine's jit buckets at construction
(first plans otherwise pay compile time).

Transactional protocol
----------------------
``plan(workload)`` runs Filtering → Sorting against a copy-on-write
`ClusterView` and returns a `Transaction` holding a unified
`SchedulingDecision` (kind ∈ placed | preempted | rejected).  Nothing is
mutated until ``txn.commit()``; dropping or ``rollback()``-ing a planned
transaction is free, which makes the Table 4 "independent preemptions"
protocol a pure read.  ``plan_batch([...])`` plans several pending
preemptors against one shared view so the decisions compose; with a
``batch_factory`` engine (``imp_batched``) ALL requests' sourcing is ONE
dispatch vmapped over a request axis, and each plan's sequential
planned-eviction semantics are preserved by masking its delta nodes out of
the precomputed tensors on device and re-sourcing only those rows.
``plan_batch`` sourcing sessions PERSIST across calls for ``imp_batched``
(invalidated through ``invalidate_node``), so bursty admission reuses the
big vmapped dispatch.  ``schedule`` / ``preempt`` / ``schedule_or_preempt``
are plan-and-commit conveniences, and the deprecated ``undo(decision)``
shim delegates to ``Transaction.rollback()``.

Latency accounting mirrors the paper's overhead analysis: we time the
candidate-sourcing phase ("the primary contributor to time overhead").  For
``fused_filter`` engines the number necessarily INCLUDES Filtering — it
happens inside the same dispatch — and for ``fused_place`` engines it spans
the whole chained dispatch, normal cycle and placement included.
"""
from __future__ import annotations

import inspect
import time
import warnings
from typing import Callable, Iterable

from .. import obs
from . import preemption, preemption_jax  # noqa: F401  (self-register engines)
from .cluster import Cluster, ClusterView
from .decisions import SchedulingDecision, Transaction
from .engines import (EngineName, SourcingEngine, get_engine,
                      registered_engines)
from .placement import (INFEASIBLE, Placement, best_tier, is_topology_hit,
                        place, place_blind)
from .preemption_jax import ShortlistConfig
from .scoring import DEFAULT_ALPHA, Candidate
from .workload import TopoPolicy, WorkloadSpec

#: ``engine="auto"`` node-count routing threshold: below it the
#: single-device fused engine wins (the mesh-sharded engine pays a fixed
#: cross-shard dispatch floor — the committed 24-node scale rows show
#: ~9.0ms sharded vs ~1.1ms batched plan-e2e P50); at or above it the
#: sharded node axis pays for itself.  Override per scheduler with
#: ``TopoScheduler(..., auto_threshold=...)``.
AUTO_ENGINE_THRESHOLD = 4096


class _LazyBatchSession:
    """Defers the engine's batch-sourcing session (device snapshot + the
    vmapped all-requests dispatch) until a plan actually reaches the
    preemption phase — a batch fully satisfied by the normal cycle never
    pays for it.  Safe because the session snapshots the BASE cluster,
    which planning never mutates.

    For ``fused_place`` engines, ``plan`` keeps that laziness on the
    device path: while no plan has needed preemption, each plan is one
    cheap standalone normal-cycle dispatch (``normal_fn``); the first
    normal-cycle failure constructs the session, and every plan from then
    on is the session's single merged normal+preemptive dispatch."""

    def __init__(self, factory, normal_fn=None) -> None:
        self._factory = factory
        self._normal_fn = normal_fn
        self._session = None

    def source(self, view, workload, index):
        if self._session is None:
            self._session = self._factory()
        return self._session.source(view, workload, index)

    def plan(self, view, workload, index):
        if self._session is None and self._normal_fn is not None:
            got = self._normal_fn(view, workload)
            if got is not None:
                from .preemption_jax import FusedPlanResult

                return FusedPlanResult("placed", got[0], got[1])
        if self._session is None:
            self._session = self._factory()
        return self._session.plan(view, workload, index)


class TopoScheduler:
    """Algorithm 1 scheduler over a pluggable sourcing engine (module
    docstring above for the pipeline).

    Engine selection: pass a registered engine name, or ``engine="auto"``
    to route by cluster size — ``imp_batched`` below ``auto_threshold``
    nodes (default `AUTO_ENGINE_THRESHOLD`), ``imp_sharded`` at or above
    it.  The resolved name is in ``self.engine``; every decision carries
    the routing in ``sourcing_provenance``.

    Shortlist sourcing knobs (engines registered with
    ``supports_shortlist`` — ``imp_batched``/``imp_sharded``; the
    ``*_full`` oracles and host engines ignore them):

    * ``shortlist_k`` — representative rows the stage-1 equivalence-class
      prescreen keeps for the exact stage-2 subset sweep (0 disables the
      shortlist entirely).  Only active when the cluster has more rows
      than ``k``.
    * ``shortlist_mode`` — ``"guaranteed"`` (default) re-dispatches the
      full sweep whenever the admissible-bound certainty check cannot
      prove the shortlist winner globally optimal, keeping decisions
      bit-identical to the full sweep; ``"best_effort"`` returns the
      fixed-K winner regardless, capping plan latency for admission
      control.
    """

    def __init__(
        self,
        cluster: Cluster,
        engine: EngineName = "imp",
        alpha: float = DEFAULT_ALPHA,
        topology_aware_placement: bool | None = None,
        warmup: bool = False,
        shortlist_k: int = 128,
        shortlist_mode: str = "guaranteed",
        auto_threshold: int | None = None,
    ) -> None:
        self.cluster = cluster
        self.auto_threshold = (AUTO_ENGINE_THRESHOLD if auto_threshold is None
                               else auto_threshold)
        self._auto = engine == "auto"
        if self._auto:
            engine = ("imp_batched"
                      if cluster.num_nodes < self.auto_threshold
                      else "imp_sharded")
        self.engine: EngineName = engine
        self._engine: SourcingEngine = get_engine(engine)
        self.alpha = alpha
        self.shortlist = (
            ShortlistConfig(k=shortlist_k, mode=shortlist_mode)
            if (shortlist_k > 0
                and getattr(self._engine, "supports_shortlist", False))
            else None)
        self._provenance = {
            "engine": engine, "auto": self._auto,
            "auto_threshold": self.auto_threshold,
            "shortlist_k": (self.shortlist.k if self.shortlist else 0),
            "shortlist_mode": (self.shortlist.mode if self.shortlist
                               else None),
        }
        # engines that fuse Guaranteed Filtering into their dispatch get
        # nodes=None and the host filter loop is skipped entirely
        self._fused_filter = bool(getattr(self._engine, "fused_filter",
                                          False))
        # fused engines run the Eq. 2 selection inside sourcing and need the
        # scheduler's alpha; pass it iff the engine's signature accepts it
        # (custom engine objects with the legacy 3-arg source_all still work)
        try:
            sig = inspect.signature(self._engine.source_all)
            self._source_takes_alpha = "alpha" in sig.parameters
        except (TypeError, ValueError):
            self._source_takes_alpha = False
        # Local (node-internal) allocation is kubelet-style topology-aware for
        # ALL engines — the paper's baseline miss comes from topology-blind
        # victim/node selection freeing badly-distributed resources, not from
        # a dumb local allocator.  Pass False explicitly for the blind-allocator
        # ablation.
        self.topology_aware = (
            True if topology_aware_placement is None else topology_aware_placement
        )
        # fused_place engines run BOTH Algorithm 1 cycles (normal-cycle
        # argmin + Sorting + Eq. 2 + §3.4 placement masks) inside one
        # dispatch; the host _plan_normal/_place_on loops are skipped.  The
        # blind-allocator ablation keeps the host path (the device scorer
        # is the topology-aware allocator).
        self._fused_place = (self.topology_aware
                             and bool(getattr(self._engine, "fused_place",
                                              False)))
        #: number of the latest ``plan()``; its decision carries it, so the
        #: ``sched.plan`` and ``sched.commit`` spans of one decision share it
        self.seq = 0
        self.listeners: list[Callable[[SchedulingDecision, str], None]] = []
        if warmup:
            warm = getattr(self._engine, "warmup", None)
            if callable(warm):
                if self.shortlist is not None:
                    warm(cluster, self.alpha, shortlist=self.shortlist)
                else:
                    warm(cluster, self.alpha)

    # ---- commit/rollback observers ------------------------------------------------
    def add_listener(self, fn: Callable[[SchedulingDecision, str], None]) -> None:
        """Subscribe to committed/rolled-back decisions (e.g. the agent fleet)."""
        self.listeners.append(fn)

    def remove_listener(self, fn: Callable[[SchedulingDecision, str], None]) -> None:
        """Unsubscribe a decision listener (missing listeners are a no-op) —
        lets transient consumers (a finished co-location run) detach without
        keeping the scheduler alive through the callback."""
        try:
            self.listeners.remove(fn)
        except ValueError:
            pass

    def _notify(self, decision: SchedulingDecision, event: str) -> None:
        for fn in self.listeners:
            fn(decision, event)

    # ---- request helpers -------------------------------------------------------
    def _request(self, workload: WorkloadSpec) -> tuple[int, int, bool]:
        spec = self.cluster.spec
        return (
            workload.gpus_per_instance,
            workload.coregroups_per_instance(spec.coregroup_size),
            workload.numa_policy == TopoPolicy.GUARANTEED,
        )

    def _place_on(self, workload: WorkloadSpec, node: int,
                  view: ClusterView) -> Placement | None:
        spec = self.cluster.spec
        free_gpu, free_cg = view.free_masks(node)
        need_gpus, need_cgs, bundle = self._request(workload)
        if self.topology_aware:
            p = place(spec, free_gpu, free_cg, need_gpus, need_cgs, bundle)
            if p is not None:
                return p
            # kubelet best-effort admission: resources fit by count but not by
            # topology — admit degraded (this is the paper's
            # TopologyAffinityError / degraded-performance case, counted as a
            # miss).  FlexTopo engines never reach this branch because their
            # candidates are topology-feasible by construction.
            return place_blind(spec, free_gpu, free_cg, need_gpus, need_cgs)
        return place_blind(spec, free_gpu, free_cg, need_gpus, need_cgs)

    def _hit(self, workload: WorkloadSpec, placement: Placement) -> bool:
        need_gpus, need_cgs, bundle = self._request(workload)
        return is_topology_hit(self.cluster.spec, placement.gpu_mask,
                               placement.cg_mask, need_gpus, need_cgs, bundle)

    # ---- planning: normal scheduling cycle ----------------------------------------
    def _plan_normal(self, workload: WorkloadSpec,
                     view: ClusterView) -> tuple[int, Placement] | None:
        best: tuple[tuple, int, Placement] | None = None
        need_gpus, need_cgs, _ = self._request(workload)
        for node in range(view.num_nodes):
            free_gpu, free_cg = view.free_masks(node)
            # count pre-screen: placement (topology-aware or blind) can
            # never succeed without enough free bits — skips the expensive
            # per-node placement construction on saturated clusters
            if (free_gpu.bit_count() < need_gpus
                    or free_cg.bit_count() < need_cgs):
                continue
            p = self._place_on(workload, node, view)
            if p is None:
                continue
            if not self._engine.topology_aware:
                # default scheduler: first node that fits
                best = ((0,), node, p)
                break
            free_gpu, _ = view.free_masks(node)
            leftover = free_gpu.bit_count() - workload.gpus_per_instance
            key = (p.tier, leftover, node)   # best tier, then best-fit
            if best is None or key < best[0]:
                best = (key, node, p)
        if best is None:
            return None
        _, node, placement = best
        return node, placement

    # ---- planning: preemption ------------------------------------------------------
    def _guaranteed_filter(self, workload: WorkloadSpec,
                           view: ClusterView) -> list[int]:
        """Alg. 1 Filtering: nodes feasible under hypothetical full drain."""
        spec = self.cluster.spec
        need_gpus, need_cgs, bundle = self._request(workload)
        nodes = []
        for node in range(view.num_nodes):
            free_gpu, free_cg = view.free_masks(node)
            for v in view.victims_on(node, workload.priority):
                free_gpu |= v.gpu_mask
                free_cg |= v.cg_mask
            if not self._engine.topology_aware:
                ok = (free_gpu.bit_count() >= need_gpus
                      and free_cg.bit_count() >= need_cgs)
            elif workload.numa_policy == TopoPolicy.GUARANTEED:
                ok = best_tier(spec, free_gpu, free_cg, need_gpus, need_cgs,
                               bundle) != INFEASIBLE
            else:  # best-effort QoS: no topology constraint during Filtering
                ok = (free_gpu.bit_count() >= need_gpus
                      and free_cg.bit_count() >= need_cgs)
            if ok:
                nodes.append(node)
        return nodes

    def _plan_preempt(
        self, workload: WorkloadSpec, view: ClusterView,
        session=None, index: int = 0,
    ) -> tuple[SchedulingDecision, int | None]:
        if session is not None:
            # plan_batch fast path: sourcing was vmapped over the request
            # axis at session start; this merges request `index`'s result
            # with the view's delta rows (Filtering fused in-dispatch)
            t0 = time.perf_counter()
            candidates: list[Candidate] = session.source(view, workload,
                                                         index)
        elif self._fused_filter:
            # Guaranteed Filtering runs inside the engine's dispatch over
            # the device-resident state: no host node loop, nodes=None
            t0 = time.perf_counter()
            if self.shortlist is not None:
                candidates = self._engine.source_all(
                    view, workload, None, alpha=self.alpha,
                    shortlist=self.shortlist)
            else:
                candidates = self._engine.source_all(view, workload, None,
                                                     alpha=self.alpha)
        else:
            nodes = self._guaranteed_filter(workload, view)
            if not nodes:
                return SchedulingDecision(kind="rejected",
                                          workload=workload), None
            t0 = time.perf_counter()
            if self._source_takes_alpha:
                candidates = self._engine.source_all(
                    view, workload, nodes, alpha=self.alpha)
            else:
                candidates = self._engine.source_all(view, workload, nodes)
        sourcing_us = (time.perf_counter() - t0) * 1e6
        if not candidates:
            return SchedulingDecision(kind="rejected", workload=workload,
                                      sourcing_us=sourcing_us), None
        chosen = self._engine.select(candidates, self.alpha)
        # fused engines already placed the winner on device (§3.4 scorer in
        # the same dispatch): bind the decoded masks instead of re-running
        # the host place() loops on the winning node
        placement = None
        if self.topology_aware:
            placement = getattr(candidates, "placements", {}).get(
                (chosen.node, chosen.victims))
        return self._bind_preemption(
            workload, view, chosen.node, chosen.victims, placement,
            sourcing_us,
            # fused engines return a winner shortlist but report the true
            # evaluated-candidate count via CandidateShortlist.n_candidates
            getattr(candidates, "n_candidates", len(candidates)))

    def _bind_preemption(
        self, workload: WorkloadSpec, view: ClusterView, node: int,
        victims: tuple[int, ...], placement: Placement | None,
        sourcing_us: float, num_candidates: int,
    ) -> tuple[SchedulingDecision, int | None]:
        """Shared preemption tail: plan the evictions, fall back to the
        host placement loops when no device masks came back, and bind."""
        for uid in victims:
            view.plan_evict(uid)
        if placement is None:
            placement = self._place_on(workload, node, view)
        if placement is None:  # cannot happen if engines are correct
            raise RuntimeError("victim set freed insufficient resources")
        planned = view.plan_bind(workload, node, placement)
        return SchedulingDecision(
            kind="preempted", workload=workload, node=node,
            placement=placement, hit=self._hit(workload, placement),
            victims=tuple(victims), sourcing_us=sourcing_us,
            num_candidates=num_candidates,
        ), planned.uid

    def _plan_fused(
        self, workload: WorkloadSpec, view: ClusterView,
        allow_preempt: bool, session=None, index: int = 0,
    ) -> Transaction:
        """One-dispatch Algorithm 1 for ``fused_place`` engines.

        The engine's chained program (or the batch session's merged
        per-request dispatch) returns either the normal-cycle winner or
        the preemption winner, both WITH concrete placement masks from the
        device §3.4 scorer — no host node loop, no host ``place()``.  The
        recorded ``sourcing_us`` spans the whole dispatch (normal cycle
        and Filtering included, they are the same program)."""
        t0 = time.perf_counter()
        if session is not None:
            res = session.plan(view, workload, index)
        elif self.shortlist is not None:
            res = self._engine.plan_fused(view, workload, self.alpha,
                                          allow_preempt,
                                          shortlist=self.shortlist)
        else:
            res = self._engine.plan_fused(view, workload, self.alpha,
                                          allow_preempt)
        sourcing_us = (time.perf_counter() - t0) * 1e6
        with obs.span("sched.bind"):
            planned_uid = None
            if res.kind == "rejected":
                decision = SchedulingDecision(
                    kind="rejected", workload=workload,
                    sourcing_us=sourcing_us, num_candidates=res.n_candidates)
            elif res.kind == "placed":
                planned_uid = view.plan_bind(workload, res.node,
                                             res.placement).uid
                decision = SchedulingDecision(
                    kind="placed", workload=workload, node=res.node,
                    placement=res.placement,
                    hit=self._hit(workload, res.placement),
                    sourcing_us=sourcing_us)
            else:
                # res.placement is None for python-fallback winners: host
                # place()
                decision, planned_uid = self._bind_preemption(
                    workload, view, res.node, res.victims, res.placement,
                    sourcing_us, res.n_candidates)
            return self._transaction(workload, decision, view, planned_uid)

    def _transaction(self, workload: WorkloadSpec,
                     decision: SchedulingDecision | None, view: ClusterView,
                     planned_uid: int | None) -> Transaction:
        """Wrap a planned decision (None: rejected) in its `Transaction`."""
        if decision is None:
            decision = SchedulingDecision(kind="rejected", workload=workload)
        decision.sourcing_provenance = dict(self._provenance)
        decision.seq = self.seq
        return Transaction(cluster=self.cluster, decision=decision,
                           on_event=self._notify, view=view,
                           planned_uid=planned_uid)

    # ---- the transactional entry points --------------------------------------------
    def plan(self, workload: WorkloadSpec, *, view: ClusterView | None = None,
             allow_normal: bool = True,
             allow_preempt: bool = True,
             _session=None, _index: int = 0) -> Transaction:
        """Evaluate one request Filtering → Sorting without mutating the cluster.

        Returns a `Transaction` whose ``decision`` is fully evaluated (node,
        placement, victims, topology hit, sourcing latency).  Call
        ``commit()`` to bind it for real, or drop/``rollback()`` it for a
        free independent evaluation.  Pass a shared ``view`` to compose
        several plans against one snapshot (see ``plan_batch``).
        """
        self.seq += 1
        with obs.span("sched.plan", seq=self.seq):
            return self._plan(workload, view, allow_normal, allow_preempt,
                              _session, _index)

    def _plan(self, workload: WorkloadSpec, view: ClusterView | None,
              allow_normal: bool, allow_preempt: bool, session,
              index: int) -> Transaction:
        view = view if view is not None else ClusterView(self.cluster)
        if (self._fused_place and allow_normal
                and (session is None or hasattr(session, "plan"))):
            # end-to-end device-resident Algorithm 1: BOTH cycles — the
            # normal-cycle argmin, Filtering, Sorting, Eq. 2 selection AND
            # the §3.4 placement masks — run in ONE dispatch (the engine's
            # chained program, or the batch session's merged per-request
            # dispatch)
            return self._plan_fused(workload, view, allow_preempt,
                                    session=session, index=index)
        decision: SchedulingDecision | None = None
        planned_uid: int | None = None
        if allow_normal:
            # fused_place engines run the normal cycle on device even when
            # a custom session lacks the merged plan; host engines loop here
            normal = (self._engine.plan_normal(view, workload)
                      if self._fused_place
                      else self._plan_normal(workload, view))
            if normal is not None:
                node, placement = normal
                planned_uid = view.plan_bind(workload, node, placement).uid
                decision = SchedulingDecision(
                    kind="placed", workload=workload, node=node,
                    placement=placement, hit=self._hit(workload, placement),
                )
        if decision is None and allow_preempt:
            decision, planned_uid = self._plan_preempt(
                workload, view, session=session, index=index)
        return self._transaction(workload, decision, view, planned_uid)

    def plan_batch(self, workloads: Iterable[WorkloadSpec],
                   allow_preempt: bool = True) -> list[Transaction]:
        """Plan several pending requests against ONE cluster snapshot.

        All plans share a copy-on-write view: request *i+1* sees request
        *i*'s planned evictions and binds, so the returned transactions can
        be committed together in order.  With a ``batch_factory`` engine
        (``imp_batched``) the whole batch's Filtering + sourcing is ONE jit
        dispatch vmapped over the request axis against the device-resident
        snapshot; each plan then merges its own result with the view's
        delta rows on device, which preserves the sequential semantics
        bitwise (parity with per-request planning is pinned in
        tests/test_fused_sourcing.py).
        """
        workloads = list(workloads)
        view = ClusterView(self.cluster)
        session = None
        if allow_preempt and len(workloads) > 1:
            starter = getattr(self._engine, "start_batch", None)
            if callable(starter):
                if getattr(self._engine, "batch_factory", None) is not None:
                    # defer the snapshot + vmapped dispatch until a plan
                    # actually reaches the preemption phase
                    batch = tuple(workloads)
                    session = _LazyBatchSession(
                        lambda: starter(self.cluster, batch, self.alpha),
                        normal_fn=(self._engine.plan_normal
                                   if self._fused_place else None))
                else:
                    # custom engine object: honor whatever it returns
                    session = starter(self.cluster, tuple(workloads),
                                      self.alpha)
        return [self.plan(wl, view=view, allow_preempt=allow_preempt,
                          _session=session, _index=i)
                for i, wl in enumerate(workloads)]

    # ---- plan-and-commit conveniences ----------------------------------------------
    def schedule(self, workload: WorkloadSpec) -> SchedulingDecision:
        """Normal cycle only; commits immediately (kind placed | rejected)."""
        return self.plan(workload, allow_preempt=False).commit()

    def preempt(self, workload: WorkloadSpec) -> SchedulingDecision:
        """Preemption only; commits immediately (kind preempted | rejected)."""
        return self.plan(workload, allow_normal=False).commit()

    def schedule_or_preempt(self, workload: WorkloadSpec) -> SchedulingDecision:
        """Full Algorithm 1; commits immediately."""
        return self.plan(workload).commit()

    # ---- undo (compat shim over Transaction.rollback) -------------------------------
    def undo(self, decision: SchedulingDecision) -> None:
        """Reverse a committed decision (Table 4 protocol evaluates each of
        the 50 scale-ups independently on the same saturated state).

        .. deprecated:: read ``plan()`` decisions without committing, or
           call ``decision.txn.rollback()`` directly; this shim delegates to
           `Transaction.rollback`, which restores every victim with its
           original uid and full placement.
        """
        warnings.warn(
            "TopoScheduler.undo() is deprecated; use Transaction.rollback() "
            "(decision.txn.rollback()) or read plan() decisions without "
            "committing", DeprecationWarning, stacklevel=2)
        if decision.txn is None:
            raise ValueError("decision has no transaction to roll back")
        decision.txn.rollback()


if __doc__ is not None:  # None under python -OO (docstrings stripped)
    __doc__ = __doc__.format(engines=" | ".join(registered_engines()))
