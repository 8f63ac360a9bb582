"""Cluster state: N servers × FlexTopo + the instance registry.

The scheduler and simulator mutate cluster state exclusively through this
class so that the FlexTopo graphs, the bitmask arrays, and the instance
registry can never diverge.  ``arrays()`` exports the dense engine view used
by the vectorized/Pallas preemption engines, ``sourcing_context()`` hands
out the incrementally-maintained host `SourcingContext` mirror, and
``device_state()`` hands out the `DeviceClusterState` — the struct-of-arrays
copy of the sourcing rows that stays RESIDENT on the accelerator across
plans.  ``invalidate_node`` marks single rows dirty in both; the device copy
re-uploads only those rows as one ``.at[rows].set()`` scatter per sync, so a
``plan()`` never re-uploads the whole ``[N, M]`` state host→device.
"""
from __future__ import annotations

import bisect
import dataclasses
import hashlib
import itertools
from collections import Counter
from functools import lru_cache, partial
from typing import Callable

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import obs
from .flextopo import FlexTopo
from .placement import Placement
from .topology import ServerSpec
from .workload import Instance, WorkloadSpec

#: Widest per-node victim row the dense sourcing arrays encode.  Nodes
#: holding more preemptible instances than this overflow the row and are
#: sourced through the per-node python engine instead (see
#: ``preemption_jax``) — the batched engines degrade gracefully rather than
#: crash.
MAX_DENSE_VICTIMS = 16


@dataclasses.dataclass
class ClusterArrays:
    """Dense snapshot for the vectorized engines."""

    free_gpu: np.ndarray      # int32[N] free-GPU bitmask per node
    free_cg: np.ndarray       # int32[N] free-CoreGroup bitmask per node
    numa_gpu_masks: np.ndarray    # int32[U]
    numa_cg_masks: np.ndarray     # int32[U]
    socket_of_numa: np.ndarray    # int32[U]


class Cluster:
    def __init__(self, spec: ServerSpec, num_nodes: int,
                 node_index: bool = True) -> None:
        self.spec = spec
        self.num_nodes = num_nodes
        self.topos = [FlexTopo(spec, node_name=f"node-{i}") for i in range(num_nodes)]
        self.instances: dict[int, Instance] = {}
        self._uid = itertools.count()
        # per-node instance index + cached free masks: turns victims_on /
        # free_masks from O(total instances) scans into O(node) lookups
        # (§Perf scheduler hillclimb; node_index=False is the naive baseline)
        self.node_index = node_index
        self._by_node: list[set[int]] = [set() for _ in range(num_nodes)]
        self._mask_cache: list[tuple[int, int] | None] = [None] * num_nodes
        # node-dirty fan-out: every mutation funnels through invalidate_node,
        # which notifies subscribers (the SourcingContext) so dense engine
        # rows refresh incrementally instead of rebuilding from instance lists
        self._dirty_listeners: list[Callable[[int], None]] = []
        # op fan-out: bind/evict/restore ALSO publish the exact mutation
        # (node, ±1, gpu_mask, cg_mask, priority, uid, preemptible) so the
        # sourcing mirror can replay dirty rows vectorized instead of
        # re-encoding each one from the instance lists (`encode_row`)
        self._op_listeners: list[Callable[[tuple], None]] = []
        # instance fan-out: the same bind/evict/restore stream with the
        # WHOLE Instance attached (workload identity included, which the
        # mask-level op tuple deliberately omits) — what the O(delta)
        # simulation layer maintains its aggregate rate accumulators,
        # replica indexes, and free-count feasibility gates from
        self._inst_listeners: list[Callable[[int, "Instance"], None]] = []
        self._sourcing_ctx: "SourcingContext | None" = None
        self._device_state: "DeviceClusterState | None" = None

    # ---- mutation -----------------------------------------------------------------
    def bind(self, workload: WorkloadSpec, node: int, placement: Placement) -> Instance:
        inst = Instance(uid=next(self._uid), workload=workload, node=node,
                        gpu_mask=placement.gpu_mask, cg_mask=placement.cg_mask)
        gpus = [g for g in range(self.spec.num_gpus) if placement.gpu_mask >> g & 1]
        cgs = [c for c in range(self.spec.num_coregroups) if placement.cg_mask >> c & 1]
        self.topos[node].allocate(inst.name, gpus, cgs)
        self.instances[inst.uid] = inst
        self._by_node[node].add(inst.uid)
        self._emit_op(node, +1, inst)
        self._emit_inst(+1, inst)
        self.invalidate_node(node)
        return inst

    def evict(self, uid: int) -> Instance:
        inst = self.instances.pop(uid)
        self.topos[inst.node].release(inst.name)
        self._by_node[inst.node].discard(uid)
        self._emit_op(inst.node, -1, inst)
        self._emit_inst(-1, inst)
        self.invalidate_node(inst.node)
        return inst

    def restore(self, inst: Instance) -> Instance:
        """Re-insert a previously evicted instance with full fidelity.

        Unlike ``bind``, the instance keeps its original uid, node, and
        GPU/CoreGroup masks — this is what ``Transaction.rollback`` uses so
        that reversing a preemption is bitwise-exact.
        """
        if inst.uid in self.instances:
            raise ValueError(f"uid {inst.uid} already bound")
        gpus = [g for g in range(self.spec.num_gpus) if inst.gpu_mask >> g & 1]
        cgs = [c for c in range(self.spec.num_coregroups) if inst.cg_mask >> c & 1]
        self.topos[inst.node].allocate(inst.name, gpus, cgs)
        self.instances[inst.uid] = inst
        self._by_node[inst.node].add(inst.uid)
        self._emit_op(inst.node, +1, inst)
        self._emit_inst(+1, inst)
        self.invalidate_node(inst.node)
        return inst

    def invalidate_node(self, node: int) -> None:
        """Single choke point for node-state changes: drops the free-mask
        cache and notifies dirty listeners (incremental sourcing arrays)."""
        self._mask_cache[node] = None
        for fn in self._dirty_listeners:
            fn(node)

    def add_dirty_listener(self, fn: Callable[[int], None]) -> None:
        """Subscribe to per-node invalidation events (bind/evict/restore)."""
        self._dirty_listeners.append(fn)

    def _emit_op(self, node: int, delta: int, inst: Instance) -> None:
        if self._op_listeners:
            op = (node, delta, inst.gpu_mask, inst.cg_mask, inst.priority,
                  inst.uid, inst.preemptible)
            for fn in self._op_listeners:
                fn(op)

    def _emit_inst(self, delta: int, inst: Instance) -> None:
        for fn in self._inst_listeners:
            fn(delta, inst)

    def add_inst_listener(self, fn: Callable[[int, Instance], None]) -> None:
        """Subscribe to ``(±1, Instance)`` for every bind/evict/restore —
        the workload-aware sibling of `add_op_listener`.  A rollback's
        ``restore`` emits ``+1`` with the ORIGINAL instance (same uid and
        masks), so a consumer's ±1 bookkeeping is exactly reversible."""
        self._inst_listeners.append(fn)

    def add_op_listener(self, fn: Callable[[tuple], None]) -> None:
        """Subscribe to the exact mutation stream behind ``invalidate_node``:
        one ``(node, ±1, gpu_mask, cg_mask, priority, uid, preemptible)``
        tuple per bind/evict/restore.  External ``invalidate_node`` calls do
        NOT produce ops — consumers must cross-check dirty marks against op
        counts (see `SourcingContext.refresh`)."""
        self._op_listeners.append(fn)

    def sourcing_context(self) -> "SourcingContext":
        """The lazily-created incremental array cache for fused sourcing."""
        if self._sourcing_ctx is None:
            self._sourcing_ctx = SourcingContext(self)
        return self._sourcing_ctx

    def device_state(self, sharded: bool = False) -> "DeviceClusterState":
        """The lazily-created device-resident struct-of-arrays state.

        ``sharded=True`` returns (creating or replacing as needed) a state
        over `cluster_mesh()`: the same three stacked tensors, node axis
        padded to a multiple of the device count and laid out with a
        `NamedSharding` over a 1-D mesh of every local device.  The fused
        evaluators then compile to SPMD programs where the per-node class
        math runs shard-local and only the final argmax chain crosses
        shards (the `imp_sharded` engine)."""
        if sharded:
            if self._device_state is None or self._device_state.mesh is None:
                self._device_state = DeviceClusterState(self,
                                                        mesh=cluster_mesh())
            return self._device_state
        if self._device_state is None:
            self._device_state = DeviceClusterState(self)
        return self._device_state

    def device_counts(self) -> Counter:
        """The device state's ``counts`` and ``fallbacks`` together; empty
        (and no state created) when no plan has used the device."""
        dcs = self._device_state
        if dcs is None:
            return Counter()
        return dcs.counts + dcs.fallbacks

    # ---- queries --------------------------------------------------------------------
    def free_masks(self, node: int) -> tuple[int, int]:
        if self.node_index:
            cached = self._mask_cache[node]
            if cached is None:
                m = self.topos[node].as_masks()
                cached = (m.free_gpu_mask, m.free_cg_mask)
                self._mask_cache[node] = cached
            return cached
        m = self.topos[node].as_masks()
        return m.free_gpu_mask, m.free_cg_mask

    def instances_on(self, node: int) -> list[Instance]:
        if self.node_index:
            return [self.instances[u] for u in self._by_node[node]]
        return [i for i in self.instances.values() if i.node == node]

    def victims_on(self, node: int, preemptor_priority: int) -> list[Instance]:
        """Potential victims: strictly lower priority and preemptible."""
        return sorted(
            (
                i for i in self.instances_on(node)
                if i.preemptible and i.priority < preemptor_priority
            ),
            key=lambda i: (i.priority, i.uid),
        )

    def arrays(self) -> ClusterArrays:
        free_gpu = np.zeros(self.num_nodes, dtype=np.int32)
        free_cg = np.zeros(self.num_nodes, dtype=np.int32)
        for n, topo in enumerate(self.topos):
            m = topo.as_masks()
            free_gpu[n] = m.free_gpu_mask
            free_cg[n] = m.free_cg_mask
        return ClusterArrays(
            free_gpu=free_gpu,
            free_cg=free_cg,
            numa_gpu_masks=self.spec.numa_gpu_masks,
            numa_cg_masks=self.spec.numa_cg_masks,
            socket_of_numa=self.spec.socket_of_numa_arr,
        )

    # ---- reporting --------------------------------------------------------------------
    def count_by_workload(self) -> dict[str, int]:
        out: dict[str, int] = {}
        for inst in self.instances.values():
            out[inst.workload.name] = out.get(inst.workload.name, 0) + 1
        return out

    def allocation_snapshot(self) -> list[dict]:
        """Fig. 8-style snapshot: per instance, its node/GPU indices and tier."""
        from .placement import achieved_tier

        rows = []
        for inst in sorted(self.instances.values(), key=lambda i: (i.node, i.uid)):
            gpus = [g for g in range(self.spec.num_gpus) if inst.gpu_mask >> g & 1]
            rows.append({
                "instance": inst.name,
                "workload": inst.workload.name,
                "node": inst.node,
                "gpus": gpus,
                "tier": achieved_tier(self.spec, inst.gpu_mask),
            })
        return rows

    def view(self) -> "ClusterView":
        """Copy-on-write planning view over the current state."""
        return ClusterView(self)

    def cross_socket_instances(self) -> int:
        """Fig. 8 headline number: instances whose GPUs span sockets."""
        from .placement import achieved_tier, min_tier_for

        return sum(
            1
            for inst in self.instances.values()
            if inst.gpu_mask
            and achieved_tier(self.spec, inst.gpu_mask)
            > min_tier_for(self.spec, inst.gpu_mask.bit_count())
        )


class ClusterView:
    """Copy-on-write overlay over a `Cluster` for transactional planning.

    Presents the same read interface the sourcing engines and the scheduler
    use (``spec``, ``num_nodes``, ``free_masks``, ``instances_on``,
    ``victims_on``) but records evictions and binds locally instead of
    mutating the base cluster.  Planned binds get *virtual* (negative) uids
    so they can never collide with live instances; ``Transaction.commit``
    later replays the plan onto the base cluster for real.

    One view can host several ``plan()`` calls (``plan_batch``): later plans
    see earlier planned evictions/binds, so a batch of decisions composes
    against a single snapshot.
    """

    def __init__(self, base: Cluster) -> None:
        self.base = base
        self.spec = base.spec
        self.num_nodes = base.num_nodes
        self._evicted: dict[int, Instance] = {}
        self._added: dict[int, Instance] = {}
        self._uid = itertools.count(-1, -1)
        # virtual uid -> real uid, filled as the view's transactions commit so
        # later transactions can resolve victims planned against earlier binds
        self.committed_uids: dict[int, int] = {}
        # per-node planned-mutation counter: lets callers (the batch
        # sourcing session) cache row encodings across plans sharing this
        # view and re-encode only rows a later plan actually touched
        self._node_version: dict[int, int] = {}

    # -- read interface (mirrors Cluster) ------------------------------------------
    def free_masks(self, node: int) -> tuple[int, int]:
        fg, fc = self.base.free_masks(node)
        for inst in self._evicted.values():
            if inst.node == node:
                fg |= inst.gpu_mask
                fc |= inst.cg_mask
        for inst in self._added.values():
            if inst.node == node:
                fg &= ~inst.gpu_mask
                fc &= ~inst.cg_mask
        return fg, fc

    def instances_on(self, node: int) -> list[Instance]:
        live = [i for i in self.base.instances_on(node)
                if i.uid not in self._evicted]
        live.extend(i for i in self._added.values() if i.node == node)
        return live

    def victims_on(self, node: int, preemptor_priority: int) -> list[Instance]:
        return sorted(
            (
                i for i in self.instances_on(node)
                if i.preemptible and i.priority < preemptor_priority
            ),
            key=lambda i: (i.priority, i.uid),
        )

    # -- planned mutations ----------------------------------------------------------
    def _bump(self, node: int) -> None:
        self._node_version[node] = self._node_version.get(node, 0) + 1

    def node_version(self, node: int) -> int:
        """Planned-mutation counter for one node (0 = untouched)."""
        return self._node_version.get(node, 0)

    def plan_evict(self, uid: int) -> Instance:
        if uid in self._added:
            inst = self._added.pop(uid)
            self._bump(inst.node)
            return inst
        inst = self.base.instances[uid]
        if uid in self._evicted:
            raise ValueError(f"uid {uid} already planned for eviction")
        self._evicted[uid] = inst
        self._bump(inst.node)
        return inst

    def plan_bind(self, workload: WorkloadSpec, node: int,
                  placement: Placement) -> Instance:
        inst = Instance(uid=next(self._uid), workload=workload, node=node,
                        gpu_mask=placement.gpu_mask, cg_mask=placement.cg_mask)
        self._added[inst.uid] = inst
        self._bump(node)
        return inst

    def resolve_uid(self, uid: int) -> int:
        """Map a virtual (planned-bind) uid to the real uid it committed as."""
        return self.committed_uids.get(uid, uid)

    def delta_nodes(self) -> set[int]:
        """Nodes whose state differs from the base cluster (planned deltas)."""
        return ({i.node for i in self._evicted.values()}
                | {i.node for i in self._added.values()})


class SourcingContext:
    """Incrementally-maintained dense arrays for the fused sourcing path.

    One row per node holds the padded bitmask/priority/uid arrays of ALL
    preemptible instances on that node (sorted by ``(priority, uid)``, the
    same order ``victims_on`` yields).  The preemptor-priority filter is NOT
    baked in: the fused evaluator masks victims by ``priority < preemptor``
    on device, so one cache serves every preemptor class.

    Invalidation semantics: the context subscribes to the cluster's
    ``invalidate_node`` choke point (hit by every ``bind``/``evict``/
    ``restore``/explicit invalidation) and marks rows dirty; ``refresh()``
    rebuilds only the dirty rows lazily before the next read.  A full
    ``plan()`` therefore touches O(dirty nodes) python state instead of
    reconstructing ``[N, M]`` arrays from instance lists.

    ``rank`` is each victim's uid-rank within its node's stored victims —
    the fused evaluator packs a combo's ranks into a bitmask whose integer
    order equals the lexicographic order of the combo's sorted uid tuple,
    reproducing ``select_best``'s victim-uid tie-break on device.

    Rows with more than `MAX_DENSE_VICTIMS` preemptible instances are marked
    ``overflow`` but still store the first `cap` victims (the lowest
    ``(priority, uid)`` prefix) plus ``next_prio``, the priority of the
    first victim NOT stored.  Because any preemptor's eligible victims
    (``priority < preemptor``) are a prefix of that order, a truncated row
    stays on the fused fast path whenever ``next_prio >= preemptor``;
    callers fall back to per-node sourcing only when eligible victims
    genuinely exceed the row (the old ``_bucket`` ValueError now degrades
    instead of crashing).
    """

    def __init__(self, cluster: Cluster, cap: int = MAX_DENSE_VICTIMS) -> None:
        self.cluster = cluster
        self.cap = cap
        n = cluster.num_nodes
        self.free_gpu = np.zeros(n, np.int32)
        self.free_cg = np.zeros(n, np.int32)
        self.vg = np.zeros((n, cap), np.int32)      # victim GPU bitmasks
        self.vc = np.zeros((n, cap), np.int32)      # victim CoreGroup bitmasks
        self.vp = np.zeros((n, cap), np.int32)      # victim priorities
        self.vu = np.zeros((n, cap), np.int64)      # victim uids
        self.rank = np.zeros((n, cap), np.int32)    # uid-rank within the node
        self.stored = np.zeros((n, cap), bool)      # slot holds an instance
        self.count = np.zeros(n, np.int32)          # preemptible instances
        self.overflow = np.zeros(n, bool)           # count > cap: truncated
        self.next_prio = np.full(n, 2**31 - 1, np.int32)  # 1st unstored prio
        self.fp = np.zeros(n, np.int64)             # equivalence-class hash
        #: fingerprint classes (`ClassIndex`), built by the first `classes`
        self._classes: ClassIndex | None = None
        self._dirty: set[int] = set(range(n))
        # journal-driven refresh: the exact mutation stream since the last
        # refresh, plus a per-node dirty-mark counter.  A dirty row whose
        # mark count equals its op count was mutated ONLY through
        # bind/evict/restore and replays vectorized; anything else (external
        # invalidation, truncated base row, giant op bursts) falls back to
        # `encode_row`.  Rows never encoded at all (`_fresh`) always do.
        self._journal: list[tuple] = []
        self._marks: dict[int, int] = {}
        self._fresh: set[int] = set(range(n))
        cluster.add_dirty_listener(self._mark)
        cluster.add_op_listener(self._journal.append)

    def _mark(self, node: int) -> None:
        self._dirty.add(node)
        self._marks[node] = self._marks.get(node, 0) + 1

    def refresh(self) -> None:
        """Bring every dirty row up to date.

        Rows whose dirt is fully explained by the op journal are replayed
        in ONE vectorized numpy merge (`_replay_journal`) — a ``plan()``
        after a burst of commits costs O(dirty rows) numpy instead of an
        `encode_row` python loop (victim sort + instance-list scan per
        row).  The rest fall back to `refresh_row`."""
        if not self._dirty:
            self._journal.clear()
            self._marks.clear()
            return
        for node in self._replay_journal():
            self.refresh_row(node, self.cluster)
        self._dirty.clear()
        self._journal.clear()
        self._marks.clear()

    #: replay gate: a single row accumulating more preemptible additions
    #: than this between refreshes re-encodes instead (bounds the merge
    #: scratch width)
    MAX_REPLAY_ADDS = 64

    def _replay_journal(self) -> set[int]:
        """Vectorized journal replay over the replay-safe dirty rows.
        Returns the rows that still need a full `encode_row` rebuild."""
        ops_by_node: dict[int, list[tuple]] = {}
        for op in self._journal:
            ops_by_node.setdefault(op[0], []).append(op)
        bad: set[int] = set()
        rows: list[int] = []
        descs: list[tuple] = []     # (keep bool[cap], adds list)
        max_adds = 1
        for node in self._dirty:
            ops = ops_by_node.get(node, ())
            if (node in self._fresh or self.overflow[node]
                    or self._marks.get(node, 0) != len(ops)):
                bad.add(node)
                continue
            # net out the ops: a bind+evict (or evict+restore) of the same
            # uid inside one window cancels exactly
            present: dict[int, tuple] = {}
            removed: set[int] = set()
            fg, fc = int(self.free_gpu[node]), int(self.free_cg[node])
            ok = True
            for _, delta, gm, cm, prio, uid, preempt in ops:
                if delta > 0:
                    fg &= ~gm
                    fc &= ~cm
                    if preempt:
                        if uid in removed:
                            # evict -> restore cancels: the victim never
                            # left the base row
                            removed.discard(uid)
                        else:
                            present[uid] = (prio, uid, gm, cm)
                else:
                    fg |= gm
                    fc |= cm
                    if preempt:
                        if uid in present:
                            del present[uid]
                        else:
                            removed.add(uid)
            if removed:
                slot_uids = self.vu[node][self.stored[node]]
                if not removed.issubset(set(int(u) for u in slot_uids)):
                    ok = False      # removal outside the stored prefix
            adds = sorted(present.values())
            if not ok or len(adds) > self.MAX_REPLAY_ADDS:
                bad.add(node)
                continue
            keep = self.stored[node] & ~np.isin(
                self.vu[node], np.fromiter(removed, np.int64, len(removed)))
            rows.append(node)
            descs.append((fg, fc, keep, adds))
            max_adds = max(max_adds, len(adds))
        if rows:
            self._replay_rows(rows, descs, max_adds)
        return bad

    def _replay_rows(self, rows: list[int], descs: list[tuple],
                     a: int) -> None:
        """One batched (priority, uid) lexsort merge for all replayed rows:
        surviving base victims + net-new additions, exact int64 uids."""
        cap, r = self.cap, len(rows)
        idx = np.asarray(rows, np.int64)
        s = cap + a
        prio = np.full((r, s), 2**31 - 1, np.int32)
        uid = np.full((r, s), np.iinfo(np.int64).max, np.int64)
        gm = np.zeros((r, s), np.int32)
        cm = np.zeros((r, s), np.int32)
        valid = np.zeros((r, s), bool)
        fg = np.zeros(r, np.int32)
        fc = np.zeros(r, np.int32)
        for i, (node, (nfg, nfc, keep, adds)) in enumerate(zip(rows, descs)):
            fg[i], fc[i] = nfg, nfc
            valid[i, :cap] = keep
            prio[i, :cap][keep] = self.vp[node][keep]
            uid[i, :cap][keep] = self.vu[node][keep]
            gm[i, :cap][keep] = self.vg[node][keep]
            cm[i, :cap][keep] = self.vc[node][keep]
            for j, (p_, u_, g_, c_) in enumerate(adds):
                prio[i, cap + j] = p_
                uid[i, cap + j] = u_
                gm[i, cap + j] = g_
                cm[i, cap + j] = c_
                valid[i, cap + j] = True
        order = np.lexsort((uid, prio), axis=-1)
        take = np.take_along_axis
        sv = take(valid, order, 1)
        sp = take(prio, order, 1)
        su = take(uid, order, 1)
        count = valid.sum(axis=1).astype(np.int32)
        overflow = count > cap
        self.free_gpu[idx] = fg
        self.free_cg[idx] = fc
        self.count[idx] = count
        self.overflow[idx] = overflow
        self.next_prio[idx] = np.where(overflow, sp[:, cap], 2**31 - 1)
        st = sv[:, :cap]
        self.stored[idx] = st
        self.vg[idx] = np.where(st, take(gm, order, 1)[:, :cap], 0)
        self.vc[idx] = np.where(st, take(cm, order, 1)[:, :cap], 0)
        self.vp[idx] = np.where(st, sp[:, :cap], 0)
        self.vu[idx] = np.where(st, su[:, :cap], 0)
        ukey = np.where(st, su[:, :cap], np.iinfo(np.int64).max)
        rank = np.argsort(np.argsort(ukey, axis=1, kind="stable"), axis=1)
        self.rank[idx] = np.where(st, rank, 0)
        self.refingerprint(rows)

    def refingerprint(self, rows) -> None:
        """Recompute the 64-bit equivalence-class fingerprint of ``rows``.

        The fingerprint covers exactly the fields the fused evaluators
        score — free masks, victim GPU/CG/priority columns, stored flags,
        count/overflow/next-priority routing state (the drain masks are
        derived from free|victims and add nothing) — and deliberately
        EXCLUDES uids and uid-ranks: nodes differing only in WHICH
        instances occupy the slots are interchangeable up to the winner
        argmax's uid tie-break, which fires after the node-id refinement
        and therefore never distinguishes across nodes.  Maintained
        incrementally at the same refresh choke points as the rows
        themselves, so the cost is O(dirty rows) per commit window.  Once
        the class index exists, each row whose fingerprint changed is
        recorded with its old value for `classes` to consume.
        """
        moved = None if self._classes is None else self._classes.moved
        for node in rows:
            h = hashlib.blake2b(digest_size=8)
            h.update(self.free_gpu[node].tobytes())
            h.update(self.free_cg[node].tobytes())
            h.update(self.count[node].tobytes())
            h.update(self.overflow[node].tobytes())
            h.update(self.next_prio[node].tobytes())
            h.update(self.vg[node].tobytes())
            h.update(self.vc[node].tobytes())
            h.update(self.vp[node].tobytes())
            h.update(self.stored[node].tobytes())
            new = int(np.frombuffer(h.digest(), np.int64)[0])
            if moved is not None:
                old = int(self.fp[node])
                if new != old:
                    moved.setdefault(int(node), old)
            self.fp[node] = new

    def classes(self) -> "ClassIndex":
        """The fingerprint equivalence classes of ``fp``, up to date.

        Built by one ``np.unique`` on the first call; later calls consume
        only the rows `refingerprint` recorded since, so a plan after a few
        commits costs O(changed rows), not O(nodes).  Call after
        ``refresh()``."""
        if self._classes is None:
            self._classes = ClassIndex(self.fp)
        else:
            self._classes.update(self.fp)
        return self._classes

    def refresh_row(self, node: int, source) -> None:
        """Fill one row from ``source`` (the base cluster or a ClusterView)."""
        self._fresh.discard(node)
        row = encode_row(source, node, self.cap)
        self.free_gpu[node] = row.free_gpu
        self.free_cg[node] = row.free_cg
        self.count[node] = row.count
        self.overflow[node] = row.overflow
        self.next_prio[node] = row.next_priority
        self.stored[node] = row.stored
        self.vg[node] = row.vg
        self.vc[node] = row.vc
        self.vp[node] = row.vp
        self.vu[node] = row.vu
        self.rank[node] = row.rank
        self.refingerprint((node,))


class ClassIndex:
    """Fingerprint equivalence classes of a `SourcingContext`'s rows.

    ``members`` maps each fingerprint to its member rows in ascending order;
    ``rep`` is True exactly at every class's lowest member.  `update` files
    each row recorded in ``moved`` (row -> the fingerprint it is filed under)
    into its new class, at a cost bounded by the two classes' sizes; a change
    to most rows rebuilds from scratch.
    """

    def __init__(self, fp: np.ndarray) -> None:
        self.moved: dict[int, int] = {}
        self._build(fp)

    def _build(self, fp: np.ndarray) -> None:
        obs.count("rep_rebuilds")
        uniq, first, inv = np.unique(fp, return_index=True,
                                     return_inverse=True)
        order = np.argsort(inv, kind="stable")
        cuts = np.cumsum(np.bincount(inv, minlength=len(uniq)))[:-1]
        self.members: dict[int, list[int]] = {
            int(v): m.tolist()
            for v, m in zip(uniq.tolist(), np.split(order, cuts))}
        self.rep = np.zeros(len(fp), bool)
        self.rep[first] = True
        self.moved.clear()

    def update(self, fp: np.ndarray) -> None:
        moved = self.moved
        if not moved:
            return
        if 2 * len(moved) >= max(len(fp), 2):
            self._build(fp)
            return
        rep, members = self.rep, self.members
        for row, old in moved.items():
            new = int(fp[row])
            if new == old:
                continue
            mem = members[old]
            i = bisect.bisect_left(mem, row)
            del mem[i]
            if i == 0:
                rep[row] = False
                if mem:
                    rep[mem[0]] = True
                else:
                    del members[old]
            mem = members.get(new)
            if mem is None:
                members[new] = [row]
                rep[row] = True
                continue
            i = bisect.bisect_left(mem, row)
            mem.insert(i, row)
            if i == 0:
                rep[mem[1]] = False
                rep[row] = True
        moved.clear()


@dataclasses.dataclass
class VictimRow:
    """One node's encoded dense sourcing row (padded to ``cap`` slots)."""

    free_gpu: int
    free_cg: int
    count: int
    overflow: bool           # count > cap: only the prefix is stored
    next_priority: int       # priority of the first victim NOT stored
    vg: np.ndarray           # int32[cap]
    vc: np.ndarray
    vp: np.ndarray
    vu: np.ndarray           # int64[cap]
    rank: np.ndarray
    stored: np.ndarray       # bool[cap]


def encode_row(source, node: int, cap: int) -> VictimRow:
    """Shared row encoder over any Cluster-like read interface (the base
    cluster for `SourcingContext` rows, a `ClusterView` for per-plan
    delta patches).

    When a node holds more than ``cap`` preemptible instances only the
    lowest ``(priority, uid)`` prefix is stored; ``next_priority`` lets
    callers decide per preemptor whether the eligible victims still fit.
    """
    fg, fc = source.free_masks(node)
    victims = sorted((i for i in source.instances_on(node) if i.preemptible),
                     key=lambda i: (i.priority, i.uid))
    row = VictimRow(
        free_gpu=fg, free_cg=fc, count=len(victims),
        overflow=len(victims) > cap,
        next_priority=victims[cap].priority if len(victims) > cap
        else 2**31 - 1,
        vg=np.zeros(cap, np.int32), vc=np.zeros(cap, np.int32),
        vp=np.zeros(cap, np.int32), vu=np.zeros(cap, np.int64),
        rank=np.zeros(cap, np.int32), stored=np.zeros(cap, bool),
    )
    victims = victims[:cap]
    for j, v in enumerate(victims):
        row.vg[j] = v.gpu_mask
        row.vc[j] = v.cg_mask
        row.vp[j] = v.priority
        row.vu[j] = v.uid
        row.stored[j] = True
    if victims:
        uids = np.asarray([v.uid for v in victims])
        row.rank[: len(victims)] = np.argsort(np.argsort(uids))
    return row


# ---------------------------------------------------------------------------------
# Device-resident cluster state (struct-of-arrays on the accelerator)
# ---------------------------------------------------------------------------------

#: rows of the stacked node-state tensor (``DeviceClusterState.nodestate``)
NODE_FIELDS = 5
NS_FREE_GPU, NS_FREE_CG, NS_NODE_ID, NS_OVERFLOW, NS_NEXT_PRIO = range(NODE_FIELDS)

#: rows of the stacked victim tensor (``DeviceClusterState.victims``)
VICTIM_FIELDS = 5
VF_GPU, VF_CG, VF_PRIO, VF_RANK, VF_STORED = range(VICTIM_FIELDS)

#: rows of the stacked drain tensor: free ∪ every stored victim mask — the
#: fully-drained masks Guaranteed Filtering popcounts on device
DRAIN_FIELDS = 2

#: out-of-range row index used to pad scatter/gather index vectors; dropped
#: by ``mode="drop"`` scatters and filled with zero rows by gathers
IDX_SENTINEL = 2**31 - 1

#: largest dirty set ``sync(flush=False)`` may leave pending for
#: in-dispatch overlay before forcing a real scatter (floor — see
#: `max_pending_rows` for the node-count-scaled cap)
MAX_PENDING_ROWS = 16


def max_pending_rows(num_nodes: int) -> int:
    """Node-count-scaled pending-overlay cap (power of two).

    A fixed 16-row cap forces a full-flush scatter after almost every
    commit burst at 10k nodes; scaling the cap with the node axis (~n/64,
    clamped to [`MAX_PENDING_ROWS`, 1024]) keeps overlay uploads amortized
    while the pow2 bucketing still bounds the jit-cache key space."""
    return max(MAX_PENDING_ROWS, min(1024, _pad_pow2(max(1, num_nodes // 64))))


def pack_rows(rows: list[VictimRow], node_ids, cap: int):
    """Stack encoded `VictimRow`s into the device layout.

    Returns ``(nodestate int32[NODE_FIELDS, P], victims
    int32[VICTIM_FIELDS, P, cap], drain int32[DRAIN_FIELDS, P])`` — the same
    column layout `DeviceClusterState` keeps resident, so view deltas can be
    scattered straight onto the resident arrays as a device-side overlay.
    """
    p = len(rows)
    ns = np.zeros((NODE_FIELDS, p), np.int32)
    v = np.zeros((VICTIM_FIELDS, p, cap), np.int32)
    dr = np.zeros((DRAIN_FIELDS, p), np.int32)
    for j, (node, row) in enumerate(zip(node_ids, rows)):
        ns[NS_FREE_GPU, j] = row.free_gpu
        ns[NS_FREE_CG, j] = row.free_cg
        ns[NS_NODE_ID, j] = node
        ns[NS_OVERFLOW, j] = int(row.overflow)
        ns[NS_NEXT_PRIO, j] = row.next_priority
        v[VF_GPU, j] = row.vg
        v[VF_CG, j] = row.vc
        v[VF_PRIO, j] = row.vp
        v[VF_RANK, j] = row.rank
        v[VF_STORED, j] = row.stored
        dr[0, j] = row.free_gpu | int(
            np.bitwise_or.reduce(np.where(row.stored, row.vg, 0)))
        dr[1, j] = row.free_cg | int(
            np.bitwise_or.reduce(np.where(row.stored, row.vc, 0)))
    return ns, v, dr


def pack_context_rows(ctx: "SourcingContext", idx):
    """Vectorized `pack_rows` over `SourcingContext` rows ``idx``."""
    idx = np.asarray(idx, np.int64)
    ns = np.zeros((NODE_FIELDS, len(idx)), np.int32)
    ns[NS_FREE_GPU] = ctx.free_gpu[idx]
    ns[NS_FREE_CG] = ctx.free_cg[idx]
    ns[NS_NODE_ID] = idx
    ns[NS_OVERFLOW] = ctx.overflow[idx]
    ns[NS_NEXT_PRIO] = ctx.next_prio[idx]
    stored = ctx.stored[idx]
    v = np.stack([
        ctx.vg[idx], ctx.vc[idx], ctx.vp[idx], ctx.rank[idx],
        stored.astype(np.int32),
    ]).astype(np.int32)
    dr = np.zeros((DRAIN_FIELDS, len(idx)), np.int32)
    dr[0] = ctx.free_gpu[idx] | np.bitwise_or.reduce(
        np.where(stored, ctx.vg[idx], 0), axis=1)
    dr[1] = ctx.free_cg[idx] | np.bitwise_or.reduce(
        np.where(stored, ctx.vc[idx], 0), axis=1)
    return ns, v, dr


def flatten_rows(ns, v, dr) -> np.ndarray:
    """Concatenate packed rows into ONE int32 row-major buffer.

    Host→device traffic on the plan hot path is dominated by per-array
    upload overhead, not bytes — dirty-row scatters and view-delta patches
    therefore travel as a single ``int32[P, NODE_FIELDS + VICTIM_FIELDS*cap
    + DRAIN_FIELDS]`` buffer and are split again inside the jit."""
    p = ns.shape[1]
    return np.concatenate(
        [ns.T, v.transpose(1, 0, 2).reshape(p, -1), dr.T],
        axis=1).astype(np.int32)


def unflatten_rows(buf, cap: int):
    """Inverse of `flatten_rows`; works on numpy and traced jnp arrays."""
    p = buf.shape[0]
    ns = buf[:, :NODE_FIELDS].T
    v = buf[:, NODE_FIELDS:NODE_FIELDS + VICTIM_FIELDS * cap]
    v = v.reshape(p, VICTIM_FIELDS, cap).transpose(1, 0, 2)
    dr = buf[:, NODE_FIELDS + VICTIM_FIELDS * cap:].T
    return ns, v, dr


def row_width(cap: int) -> int:
    """int32 words of one flattened row (`flatten_rows`)."""
    return NODE_FIELDS + VICTIM_FIELDS * cap + DRAIN_FIELDS


def apply_rows(ns, v, dr, idx, buf):
    """Scatter flattened rows onto the three stacked tensors (jnp ``.at``
    semantics; `IDX_SENTINEL` pad entries are dropped).  The single shared
    implementation behind both the resident-state scatter and the fused
    evaluators' in-dispatch view-delta overlay."""
    a, b, c = unflatten_rows(buf, v.shape[2])
    return (ns.at[:, idx].set(a, mode="drop"),
            v.at[:, idx, :].set(b, mode="drop"),
            dr.at[:, idx].set(c, mode="drop"))


def pack_scatter(idx, buf) -> np.ndarray:
    """A scatter's row indices and flattened rows as ONE int32 buffer
    (`apply_packed_rows` splits it again): one host→device transfer."""
    return np.concatenate([idx, buf.reshape(-1)]).astype(np.int32, copy=False)


def apply_packed_rows(ns, v, dr, packed):
    """`apply_rows` from a `pack_scatter` buffer: ``q`` indices, then
    ``q`` rows (``q`` follows from the static length and row width)."""
    q = packed.shape[0] // (1 + row_width(v.shape[2]))
    return apply_rows(ns, v, dr, packed[:q], packed[q:].reshape(q, -1))


@lru_cache(maxsize=None)
def _cluster_mesh(devices) -> jax.sharding.Mesh:
    return jax.sharding.Mesh(np.asarray(devices), ("nodes",))


def cluster_mesh(devices=None) -> jax.sharding.Mesh:
    """1-D mesh over every local device (axis ``nodes``) — the layout of
    ``Cluster.device_state(sharded=True)``.  Degrades to a one-device mesh
    when only one device exists, so the sharded paths stay parity-testable
    anywhere."""
    return _cluster_mesh(tuple(jax.devices()) if devices is None
                         else tuple(devices))


@lru_cache(maxsize=None)
def _mesh_shardings(mesh):
    """(node, victim, replicated) `NamedSharding`s of a mesh: node-axis
    tensors split their axis 1 over EVERY mesh axis."""
    axes = tuple(mesh.axis_names)
    return (NamedSharding(mesh, P(None, axes)),
            NamedSharding(mesh, P(None, axes, None)),
            NamedSharding(mesh, P()))


def mesh_program(name: str, fn, mesh, ins: str, out="r"):
    """`obs.program` of ``fn`` for a device layout: ``jit_<name>`` on one
    device (``mesh`` None), or ``jit_<name>_sharded`` over ``mesh`` with
    explicit in/out shardings, one letter per argument in ``ins`` and a
    letter (or a pytree of letters) for the result: ``n`` node-axis
    tensors, ``v`` victim tensors, ``r`` replicated (`_mesh_shardings`)."""
    if mesh is None:
        return obs.program(name, fn)
    sh = dict(zip("nvr", _mesh_shardings(mesh)))
    return obs.program(f"{name}_sharded", fn,
                       in_shardings=tuple(sh[c] for c in ins),
                       out_shardings=jax.tree.map(sh.__getitem__, out))


@lru_cache(maxsize=None)
def _scatter_rows(mesh):
    """The jitted scatter updating every dirty row of all three tensors
    from a single `pack_scatter` upload.  Over a mesh the resident tensors
    stay sharded on both sides; the packed rows replicate (O(dirty),
    tiny)."""
    return mesh_program("apply_rows", apply_packed_rows, mesh, "nvnr",
                        ("n", "v", "n"))


def _pad_pow2(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


def pack_bits(mask) -> np.ndarray:
    """``bool[n]`` as ``int32[ceil(n / 32)]`` words: mask bit ``i`` is bit
    ``i & 31`` of word ``i >> 5`` (`unpack_bits` is the traced inverse);
    bits past ``n`` are 0."""
    bits = np.zeros(-(-len(mask) // 32) * 32, bool)
    bits[:len(mask)] = mask
    return np.packbits(bits, bitorder="little").view("<i4").astype(
        np.int32, copy=False)


def unpack_bits(words, n: int):
    """Traced inverse of `pack_bits`: ``bool[n]`` from its int32 words.
    Each word is shifted by every bit position and the ``[words, 32]``
    result read in row-major order: elementwise work and a reshape, no
    gather, which the TPU runs slowly."""
    import jax.numpy as jnp

    shifts = jnp.arange(32, dtype=jnp.int32)
    bits = (words[:, None] >> shifts[None, :]) & 1
    return bits.reshape(-1)[:n] != 0


def pad_idx(ids, floor: int = 1) -> np.ndarray:
    """Pad a row-index list to a power-of-two bucket with `IDX_SENTINEL`
    (bounds jit-cache variants; sentinels drop out of scatters/gathers)."""
    p = max(floor, _pad_pow2(len(ids)))
    out = np.full(p, IDX_SENTINEL, np.int32)
    out[: len(ids)] = ids
    return out


# ---------------------------------------------------------------------------------
# Device-side view-delta encoder
# ---------------------------------------------------------------------------------

def encode_delta_core(nodestate, victims, didx, rem, fog, foc, og, oc,
                      addg, addc, addp, addv, *, cap: int, a: int):
    """Traced twin of ``pack_rows([encode_row(view, node, cap), ...])``.

    Instead of re-encoding each view-delta node's row on the host (victim
    sort + O(delta) ``free_masks`` overlay per node, then a python pack
    loop), the planned evictions/binds travel as tiny per-node descriptors
    and the patch rows are rebuilt ON DEVICE from the resident base rows:

    * gather the base row by ``didx`` (`IDX_SENTINEL` pads gather zeros),
    * drop removed base victims (``rem`` slot bitmask), apply the freed /
      newly-occupied mask deltas (``fog``/``foc`` | base, ``& ~og``/``oc``),
    * merge up to ``a`` net-new victims (``add*``, pre-sorted by uid
      ascending — planned binds carry NEGATIVE virtual uids, so every add
      orders before every base victim) via a two-pass stable argsort on
      ``(priority, uid-order)``, which reproduces ``encode_row``'s
      ``(priority, uid)`` victim sort bit-exactly without int64 uids ever
      touching the device.

    Returns the flattened patch buffer ``int32[D, NODE_FIELDS +
    VICTIM_FIELDS*cap + DRAIN_FIELDS]`` ready for the fused evaluators'
    in-dispatch overlay (`apply_rows`) — the rows never round-trip through
    python.  Rows whose merge could truncate (base overflow, > ``cap``
    final victims, > ``a`` adds) are host-encoded by the caller instead.
    """
    import jax
    import jax.numpy as jnp

    big = jnp.int32(2**31 - 1)
    ns = jnp.take(nodestate, didx, axis=1, mode="fill", fill_value=0)
    vv = jnp.take(victims, didx, axis=1, mode="fill", fill_value=0)
    fg = (ns[NS_FREE_GPU] | fog) & ~og
    fc = (ns[NS_FREE_CG] | foc) & ~oc
    slot = jnp.arange(cap, dtype=jnp.int32)
    keep = (vv[VF_STORED] != 0) & (((rem[:, None] >> slot[None, :]) & 1) == 0)
    mg = jnp.concatenate([vv[VF_GPU], addg], axis=1)
    mc = jnp.concatenate([vv[VF_CG], addc], axis=1)
    mp = jnp.concatenate([vv[VF_PRIO], addp], axis=1)
    d = didx.shape[0]
    akey = jnp.broadcast_to(jnp.arange(a, dtype=jnp.int32)[None, :], (d, a))
    mkey = jnp.concatenate([a + vv[VF_RANK], akey], axis=1)
    mvalid = jnp.concatenate([keep, addv != 0], axis=1)
    # two stable argsorts = one (priority, uid-order) lexsort
    o1 = jnp.argsort(jnp.where(mvalid, mkey, big), axis=1)
    p1 = jnp.where(jnp.take_along_axis(mvalid, o1, axis=1),
                   jnp.take_along_axis(mp, o1, axis=1), big)
    order = jnp.take_along_axis(o1, jnp.argsort(p1, axis=1), axis=1)

    def srt(x):
        return jnp.take_along_axis(x, order, axis=1)[:, :cap]

    st = srt(mvalid)
    sti = st.astype(jnp.int32)
    vg = jnp.where(st, srt(mg), 0)
    vc = jnp.where(st, srt(mc), 0)
    vp = jnp.where(st, srt(mp), 0)
    skey = jnp.where(st, srt(mkey), big)
    rnk = jnp.sum((skey[:, None, :] < skey[:, :, None]) & st[:, None, :],
                  axis=2, dtype=jnp.int32)
    rank = jnp.where(st, rnk, 0)
    new_ns = jnp.stack([fg, fc, didx,
                        jnp.zeros_like(fg), jnp.full_like(fg, big)])
    drg = fg | jax.lax.reduce(vg, np.int32(0), jax.lax.bitwise_or, (1,))
    drc = fc | jax.lax.reduce(vc, np.int32(0), jax.lax.bitwise_or, (1,))
    new_v = jnp.stack([vg, vc, vp, rank, sti])
    new_dr = jnp.stack([drg, drc])
    return jnp.concatenate(
        [new_ns.T, new_v.transpose(1, 0, 2).reshape(d, -1), new_dr.T], axis=1)


@lru_cache(maxsize=None)
def delta_encoder(mesh, cap: int, a: int):
    """Jitted `encode_delta_core` keyed by (mesh, victim cap, add bucket);
    the descriptor length ``D`` stays dynamic (pow2-padded by the caller),
    so variants are bounded by the few (cap, a) combinations in play.
    Over a mesh the descriptor columns arrive replicated and the rebuilt
    patch rows come back replicated (O(delta), tiny), so the base-row
    gather is the only cross-shard traffic."""
    return mesh_program("delta_encode",
                        partial(encode_delta_core, cap=cap, a=a), mesh,
                        "nv" + "r" * 10)


class ViewDelta:
    """Per-plan descriptor set for the device-side delta encoder.

    Built once per fused ``plan()`` from the `ClusterView`'s planned
    evictions/binds (O(delta instances) host work, no per-node victim
    sort): nodes whose patch row the device can rebuild exactly carry tiny
    descriptor columns (`device_rows` feeds them to `delta_encoder`);
    nodes behind a replay gate — resident row still pending-stale, base
    row truncated, more than ``a_max`` adds, or a post-merge victim count
    above ``cap`` — fall back to host `encode_row` (the ``fallback``
    dict).  Winner uid decode stays lazy and host-side: `row(node)`
    encodes ONE node on demand (uids are int64 and never on device).
    """

    def __init__(self, view, ctx: "SourcingContext", pending,
                 a_max: int = 8) -> None:
        self.view = view
        self.cap = cap = ctx.cap
        self.fallback: dict[int, VictimRow] = {}
        self._rows: dict[int, VictimRow] = {}
        dense: list[int] = []
        descs: list[tuple] = []
        max_adds = 1
        per: dict[int, list] = {}
        for inst in view._evicted.values():
            per.setdefault(inst.node, []).append((False, inst))
        for inst in view._added.values():
            per.setdefault(inst.node, []).append((True, inst))
        for node, insts in per.items():
            bad = (node in pending or bool(ctx.overflow[node])
                   or node in ctx._fresh)
            fog = foc = og = oc = 0
            removed: set[int] = set()
            adds: list[tuple] = []
            for is_add, inst in insts:
                if is_add:
                    og |= inst.gpu_mask
                    oc |= inst.cg_mask
                    if inst.preemptible:
                        adds.append((inst.uid, inst.priority,
                                     inst.gpu_mask, inst.cg_mask))
                else:
                    fog |= inst.gpu_mask
                    foc |= inst.cg_mask
                    if inst.preemptible:
                        removed.add(inst.uid)
            keep = ctx.stored[node] & ~np.isin(
                ctx.vu[node], np.fromiter(removed, np.int64, len(removed)))
            count = int(keep.sum()) + len(adds)
            if bad or len(adds) > a_max or count > cap:
                self.fallback[node] = self.row(node)
                continue
            rem = int(np.bitwise_or.reduce(
                np.where(ctx.stored[node] & ~keep, 1 << np.arange(cap), 0)))
            adds.sort()     # uid ascending == global (priority, uid) prep
            dense.append(node)
            descs.append((rem, fog, foc, og, oc, adds, keep))
            max_adds = max(max_adds, len(adds))
        self.a = _pad_pow2(max_adds)
        d = len(dense)
        self.dense = np.asarray(dense, np.int32)
        self.rem = np.zeros(d, np.int32)
        self.fog = np.zeros(d, np.int32)
        self.foc = np.zeros(d, np.int32)
        self.og = np.zeros(d, np.int32)
        self.oc = np.zeros(d, np.int32)
        self.addg = np.zeros((d, self.a), np.int32)
        self.addc = np.zeros((d, self.a), np.int32)
        self.addp = np.zeros((d, self.a), np.int32)
        self.addv = np.zeros((d, self.a), np.int32)
        # host routing metadata (no device round-trip): surviving base
        # priorities + add priorities per dense node
        self._vp = np.full((d, cap), 2**31 - 1, np.int32)
        self._count = np.zeros(d, np.int32)
        for i, (node, (rem, fog, foc, og, oc, adds, keep)) in enumerate(
                zip(dense, descs)):
            self.rem[i] = rem
            self.fog[i], self.foc[i] = fog, foc
            self.og[i], self.oc[i] = og, oc
            for j, (_, prio, gm, cm) in enumerate(adds):
                self.addg[i, j] = gm
                self.addc[i, j] = cm
                self.addp[i, j] = prio
                self.addv[i, j] = 1
            self._vp[i][keep] = ctx.vp[node][keep]
            self._count[i] = keep.sum() + len(adds)
        self._addp_m = np.where(self.addv != 0, self.addp, 2**31 - 1)
        self._pos = {int(n): i for i, n in enumerate(dense)}

    # -- container interface (the delta-node set) ---------------------------------
    def __len__(self) -> int:
        return len(self._pos) + len(self.fallback)

    def __contains__(self, node: int) -> bool:
        return node in self._pos or node in self.fallback

    def __iter__(self):
        yield from self._pos
        yield from self.fallback

    # -- host routing metadata ----------------------------------------------------
    def elig_bad(self, thresh: int):
        """Per delta node: eligible stored victims under ``thresh`` and
        whether truncation could hide eligible victims (dense rows never
        truncate by construction)."""
        elig = {node: int(((row.vp < thresh) & row.stored).sum())
                for node, row in self.fallback.items()}
        bad = {node: bool(row.overflow) and row.next_priority < thresh
               for node, row in self.fallback.items()}
        if len(self._pos):
            cnt = ((self._vp < thresh).sum(axis=1)
                   + (self._addp_m < thresh).sum(axis=1))
            for node, i in self._pos.items():
                elig[node] = int(cnt[i])
                bad[node] = False
        return elig, bad

    def count(self, node: int) -> int:
        i = self._pos.get(node)
        if i is not None:
            return int(self._count[i])
        return self.fallback[node].count

    def row(self, node: int) -> VictimRow:
        """Exact host row for one delta node (winner uid decode / wide
        fallbacks) — lazy, cached, O(1) nodes per plan."""
        row = self._rows.get(node)
        if row is None:
            row = self._rows[node] = encode_row(self.view, node, self.cap)
        return row

    # -- device path ---------------------------------------------------------------
    def device_rows(self, dcs: "DeviceClusterState"):
        """Encode every dense delta row on device: returns ``(didx
        int32[Dp], buf int32[Dp, width])`` pow2-padded, buf still on
        device.  Empty when all delta nodes fell back."""
        d = len(self.dense)
        if d == 0:
            return None
        didx = pad_idx(self.dense)
        dp = len(didx)

        def pad(x):
            if len(x) == dp:
                return x
            width = ((0, dp - d),) + ((0, 0),) * (x.ndim - 1)
            return np.pad(x, width)

        up = obs.upload
        buf = dcs.delta_encode(
            self.a, up(didx),
            up(pad(self.rem)), up(pad(self.fog)), up(pad(self.foc)),
            up(pad(self.og)), up(pad(self.oc)), up(pad(self.addg)),
            up(pad(self.addc)), up(pad(self.addp)), up(pad(self.addv)))
        return didx, buf


class DeviceClusterState:
    """Device-resident struct-of-arrays view of the cluster's sourcing state.

    Three stacked int32 tensors live ON DEVICE across plans:

    * ``nodestate [NODE_FIELDS, N]`` — free-GPU/CG slot masks, node id,
      overflow flag, first-unstored priority;
    * ``victims   [VICTIM_FIELDS, N, cap]`` — per-slot victim GPU/CG masks,
      priorities, uid-ranks, stored flags (the ``(priority, uid)``-sorted
      rows of the host `SourcingContext` mirror);
    * ``drain     [DRAIN_FIELDS, N]`` — per-node fully-drained masks
      (free ∪ all stored victim masks), the popcount input of the fused
      Guaranteed-Filtering step.

    ``slices`` exposes the static NUMA/socket slice layout of the SKU
    (`placement_jax.SpecSlices`) that the device-side placement scorer —
    the normal cycle and the winner's §3.4 mask selection, both fused into
    the sourcing dispatch — popcounts these tensors against.

    The host `SourcingContext` stays as the *mirror*: it keeps the int64
    victim uids (decoded only for the winner) and the counts the host needs
    for wide/overflow routing.  Both subscribe to ``invalidate_node``, so a
    ``bind``/``evict``/``restore`` marks single rows dirty; ``sync()``
    refreshes the mirror lazily and pushes ONLY the dirty rows to the device
    as one ``.at[rows].set()`` scatter — no per-plan host rebuild/upload.
    Copy-on-write `ClusterView` deltas never touch these arrays: the fused
    evaluators overlay patch rows inside the dispatch (``pack_rows``).
    """

    def __init__(self, cluster: Cluster, cap: int | None = None,
                 mesh=None) -> None:
        #: device mesh the stacked tensors are sharded over (None: one
        #: device).  Over a mesh the node axis is padded UP to a multiple
        #: of the device count; pad rows carry `IDX_SENTINEL` node ids and
        #: zero masks, which every fused core already excludes, so results
        #: are bit-identical to the unsharded layout.  The full upload, the
        #: dirty-row scatter and the view-delta encoder pin their outputs
        #: sharded or replicated, so the tensors never silently migrate.
        self.mesh = mesh
        self.cluster = cluster
        self.mirror = cluster.sourcing_context()
        if cap is not None and cap != self.mirror.cap:
            raise ValueError("device cap must match the mirror's cap")
        self.cap = self.mirror.cap
        self.max_pending = max_pending_rows(cluster.num_nodes)
        self.nodestate = None   # jnp.int32[NODE_FIELDS, n_rows]
        self.victims = None     # jnp.int32[VICTIM_FIELDS, n_rows, cap]
        self.drain = None       # jnp.int32[DRAIN_FIELDS, n_rows]
        #: host fast-path: when no node stores more than NARROW_M victims,
        #: per-plan wide/overflow routing is skipped entirely
        self.count_max = 0
        #: monotonic state counter, bumped by every invalidation: entries
        #: of ``plan_cache`` (per-preemptor routing splits + uploaded
        #: index/patch device arrays for the delta-free fast path) record
        #: the version they were built at and are ignored once it moves
        self.version = 0
        self.plan_cache: dict = {}
        #: the last ``rep_classes`` answer: (version, rep bool[n_rows])
        self._rep: tuple | None = None
        #: plans that left the device path: ``host_rows`` counts rows
        #: sourced by per-node host Python (victims past the stored
        #: prefix), ``shortlist_full_sweep`` guaranteed-mode shortlist
        #: checks that re-dispatched the full sweep
        self.fallbacks: Counter = Counter()
        #: plans and batch sessions that stayed on the device path:
        #: ``shortlist_plans`` two-stage shortlist dispatches (the
        #: denominator of ``fallbacks["shortlist_full_sweep"]``),
        #: ``batch_builds`` / ``batch_reuses`` new against reused
        #: `persistent_batch_session` sessions
        self.counts: Counter = Counter()
        self._dirty: set[int] = set(range(cluster.num_nodes))
        cluster.add_dirty_listener(self._mark_dirty)

    def _mark_dirty(self, node: int) -> None:
        self._dirty.add(node)
        self.version += 1

    def sync(self, flush: bool = True) -> "DeviceClusterState":
        """Bring the device view up to date with the live cluster.

        Dirty rows are packed host-side (O(dirty) python) and applied as a
        single scatter; a majority-dirty state falls back to one full
        upload.  Index vectors are padded to power-of-two buckets with
        `IDX_SENTINEL` so the scatter jit-cache stays small.

        ``flush=False`` refreshes the host mirror but leaves a SMALL dirty
        set resident-stale in ``pending``: the fused evaluators overlay
        those rows in-dispatch exactly like view-delta patches, saving the
        separate scatter dispatch on the plan hot path.  Large pending sets
        are flushed regardless so the overlay bucket stays small.
        """
        with obs.span("sched.sync"):
            self.mirror.refresh()
            n = self.cluster.num_nodes
            if self.nodestate is None or 2 * len(self._dirty) >= max(n, 2):
                ns, v, dr = pack_context_rows(self.mirror, np.arange(n))
                self.nodestate, self.victims, self.drain = self._upload_full(
                    ns, v, dr)
                self._dirty.clear()
            elif self._dirty and (flush
                                  or len(self._dirty) > self.max_pending):
                rows = sorted(self._dirty)
                buf = flatten_rows(*pack_context_rows(self.mirror, rows))
                idx = pad_idx(rows)
                if len(idx) > len(rows):
                    buf = np.pad(buf, ((0, len(idx) - len(rows)), (0, 0)))
                self.nodestate, self.victims, self.drain = self._scatter(
                    idx, buf)
                self._dirty.clear()
            self.count_max = int(self.mirror.count.max()) if n else 0
        return self

    @property
    def n_rows(self) -> int:
        """Length of the device node axis: ``num_nodes``, padded to a
        multiple of the device count over a mesh (the fused paths use it as
        the row base of their gathered sections)."""
        if self.mesh is None:
            return self.cluster.num_nodes
        d = int(self.mesh.size)
        return -(-max(self.cluster.num_nodes, 1) // d) * d

    def rep_classes(self) -> np.ndarray:
        """Equivalence-class representative mask over the node axis.

        A host ``bool[n_rows]`` mask that is True exactly for the
        LOWEST-index member of every fingerprint class
        (`SourcingContext.refingerprint`).  Because the fused winner argmax
        breaks score ties by lower node id before any uid comparison, the
        full-sweep winner inside a class is always its lowest-id member —
        sweeping representatives only is exact.  Call after ``sync()`` (the
        mirror fingerprints must be fresh).  The mask comes from the
        mirror's class index (`SourcingContext.classes`), which files only
        the rows whose fingerprint changed since the last call; the same
        ``version`` returns the same array, a new one a new array.  Rows
        past ``num_nodes`` (sharded padding) carry sentinel node ids and
        stay False.  The mask travels bit-packed inside each shortlist
        plan's argument buffer (`pack_bits`).
        """
        last = self._rep
        if last is not None and last[0] == self.version:
            return last[1]
        rep = np.zeros(self.n_rows, bool)
        rep[:self.cluster.num_nodes] = self.mirror.classes().rep
        self._rep = (self.version, rep)
        return rep

    def _upload_full(self, ns, v, dr):
        """Full-rebuild upload; over a mesh, sentinel pad rows first."""
        if self.mesh is None:
            return obs.upload(ns), obs.upload(v), obs.upload(dr)
        pad = self.n_rows - ns.shape[1]
        if pad:
            pns = np.zeros((NODE_FIELDS, pad), np.int32)
            pns[NS_NODE_ID] = IDX_SENTINEL
            ns = np.concatenate([ns, pns], axis=1)
            v = np.concatenate(
                [v, np.zeros((VICTIM_FIELDS, pad, v.shape[2]), np.int32)],
                axis=1)
            dr = np.concatenate(
                [dr, np.zeros((DRAIN_FIELDS, pad), np.int32)], axis=1)
        node_sh, victim_sh, _ = _mesh_shardings(self.mesh)
        return (obs.upload(np.ascontiguousarray(ns), node_sh),
                obs.upload(np.ascontiguousarray(v), victim_sh),
                obs.upload(np.ascontiguousarray(dr), node_sh))

    def _scatter(self, idx, buf):
        """The dirty-row scatter, from one packed upload."""
        return _scatter_rows(self.mesh)(self.nodestate, self.victims,
                                        self.drain,
                                        obs.upload(pack_scatter(idx, buf)))

    def delta_encode(self, a: int, didx, *descs):
        """Run the device-side view-delta encoder against the resident
        base tensors (`ViewDelta.device_rows` feeds the descriptors)."""
        return delta_encoder(self.mesh, self.cap, a)(
            self.nodestate, self.victims, didx, *descs)

    @property
    def pending(self) -> set[int]:
        """Rows whose device copy is stale (mirror is fresh after sync):
        deferred by ``sync(flush=False)`` for in-dispatch overlay."""
        return self._dirty

    @property
    def slices(self):
        """The SKU's static NUMA/socket slice layout, device-resident.

        Convenience accessor for the `repro.core.placement_jax.SpecSlices`
        of this cluster's spec (per-NUMA GPU/CoreGroup mask columns, socket
        one-hot, placement scope-membership matrix, lowest-bit selector
        tables) — the layout the fused placement scorers are traced
        against.  The jit evaluators resolve it per-spec via
        ``spec_slices`` internally; this property returns the SAME cached
        object for introspection and tests."""
        from .placement_jax import spec_slices

        return spec_slices(self.cluster.spec)
