"""Plain reference scheduler: Algorithm 1 on the host, for deciding `correct`.

A straightforward Python statement of what every decision of the system under
test must equal.  It imports nothing of the program: the server topology, the
placement rules (paper §3.4), Incremental Minimal Preemption (Algorithm 2) and
Eq. 1/Eq. 2 selection are written out here from the paper and the host
engine's documented semantics.

* Normal cycle: over every node with enough free GPUs and CoreGroups, place
  topology-aware (best tier, best-fit scope, lowest free bits), falling back to
  lowest-free-bits blind admission when only the counts fit; keep the least
  ``(tier, leftover GPUs, node)``.
* Preemption (when the normal cycle places nothing): per node, victims are the
  preemptible instances of lower priority; take the smallest subset size at
  which any subset frees a feasible placement, score every feasible subset of
  that size by Eq. 1, and keep the global Eq. 2 argmax with ties broken by
  fewer victims, lower node id, then lexically smaller victim uids.
* The preemptor is placed on the winner node after evicting its victims.

A decision is the key ``(kind, node, victims, hit, masks)`` with
``masks = (gpu_mask, cg_mask, tier)`` or None when rejected.

Per-node results are pure functions of the node's free masks, its instances in
uid order and the request, so they are memoised on exactly that input: nodes of
a tiled fleet share entries, which keeps a 10,240-node evaluation in the tens of
milliseconds.
"""
from __future__ import annotations

import copy
import dataclasses
import itertools

INFEASIBLE = 3
TIER_SCORES = (1.0, 0.5, 0.1)


@dataclasses.dataclass(frozen=True)
class Topology:
    """One server SKU: sockets, NUMA nodes, GPUs and CoreGroups as bitmasks."""

    num_sockets: int
    num_numa: int
    num_cores: int
    num_gpus: int
    coregroup_size: int

    @property
    def num_coregroups(self) -> int:
        return self.num_cores // self.coregroup_size

    @property
    def gpus_per_numa(self) -> int:
        return max(1, self.num_gpus // self.num_numa)

    @property
    def numa_per_socket(self) -> int:
        return self.num_numa // self.num_sockets

    def socket_of_numa(self, u: int) -> int:
        return u // self.numa_per_socket

    def numa_of_gpu(self, g: int) -> int:
        if self.num_gpus >= self.num_numa:
            return g // (self.num_gpus // self.num_numa)
        return g * (self.num_numa // self.num_gpus)

    def numa_of_coregroup(self, c: int) -> int:
        return c // (self.num_coregroups // self.num_numa)

    def __post_init__(self) -> None:
        gpu = [0] * self.num_numa
        cg = [0] * self.num_numa
        for g in range(self.num_gpus):
            gpu[self.numa_of_gpu(g)] |= 1 << g
        for c in range(self.num_coregroups):
            cg[self.numa_of_coregroup(c)] |= 1 << c
        object.__setattr__(self, "numa_gpu", tuple(gpu))
        object.__setattr__(self, "numa_cg", tuple(cg))
        scopes = ([[u] for u in range(self.num_numa)],
                  [[u for u in range(self.num_numa)
                    if self.socket_of_numa(u) == s]
                   for s in range(self.num_sockets)],
                  [list(range(self.num_numa))])
        object.__setattr__(self, "scopes", scopes)


@dataclasses.dataclass(frozen=True)
class Request:
    """One workload class as the scheduler sees it."""

    name: str
    priority: int
    gpus: int
    cgs: int
    bundle: bool          # NUMA policy guaranteed: GPU and CoreGroups paired
    preemptible: bool


def _lowest(mask: int, k: int, n: int) -> int | None:
    out = 0
    for i in range(n):
        if k == 0:
            break
        if mask >> i & 1:
            out |= 1 << i
            k -= 1
    return None if k else out


def _caps(t: Topology, fg: int, fc: int, per_bundle: int):
    out = []
    for u in range(t.num_numa):
        g = (fg & t.numa_gpu[u]).bit_count()
        c = (fc & t.numa_cg[u]).bit_count()
        out.append((g, c, min(g, c // per_bundle) if per_bundle else g))
    return out


def best_tier(t: Topology, fg: int, fc: int, r: Request) -> int:
    """Best tier (0 NUMA, 1 socket, 2 cross-socket) a placement can reach."""
    if r.gpus == 0:
        for tier, scopes in enumerate(t.scopes):
            for numas in scopes:
                mask = 0
                for u in numas:
                    mask |= t.numa_cg[u]
                if (fc & mask).bit_count() >= r.cgs:
                    return tier
        return INFEASIBLE
    per_bundle = r.cgs // r.gpus if r.bundle else 0
    caps = _caps(t, fg, fc, per_bundle)
    col = 2 if r.bundle else 0
    for tier, scopes in enumerate(t.scopes):
        for numas in scopes:
            if (sum(caps[u][col] for u in numas) >= r.gpus
                    and sum(caps[u][1] for u in numas) >= r.cgs):
                return tier
    return INFEASIBLE


def place(t: Topology, fg: int, fc: int, r: Request):
    """Concrete topology-aware masks ``(gpu_mask, cg_mask, tier)`` or None."""
    tier = best_tier(t, fg, fc, r)
    if tier == INFEASIBLE:
        return None
    bundled = r.bundle and r.gpus > 0
    per_bundle = r.cgs // r.gpus if bundled else 0
    caps = _caps(t, fg, fc, per_bundle)
    col = 2 if bundled else 0
    fits = []
    for numas in t.scopes[tier]:
        units = sum(caps[u][col] for u in numas)
        if units >= r.gpus and sum(caps[u][1] for u in numas) >= r.cgs:
            fits.append((units - r.gpus, numas))
    if not fits:
        return None
    _, numas = min(fits)
    gm = cm = 0
    left_g, left_c = r.gpus, r.cgs
    for u in numas:
        if left_g == 0:
            break
        take = min(left_g, caps[u][col])
        if take <= 0:
            continue
        sel = _lowest(fg & t.numa_gpu[u], take, t.num_gpus)
        if sel is None:
            return None
        gm |= sel
        left_g -= take
        if bundled and per_bundle:
            c_take = min(take * per_bundle, left_c)
            sel = _lowest(fc & t.numa_cg[u], c_take, t.num_coregroups)
            if sel is None:
                return None
            cm |= sel
            left_c -= c_take
    if left_c:
        for u in numas:
            avail = fc & t.numa_cg[u] & ~cm
            take = min(avail.bit_count(), left_c)
            if take:
                cm |= _lowest(avail, take, t.num_coregroups)
                left_c -= take
            if left_c == 0:
                break
    if left_g or left_c:
        return None
    return gm, cm, tier


def achieved_tier(t: Topology, gm: int) -> int:
    numas = {t.numa_of_gpu(g) for g in range(t.num_gpus) if gm >> g & 1}
    if len(numas) <= 1:
        return 0
    return 1 if len({t.socket_of_numa(u) for u in numas}) == 1 else 2


def place_blind(t: Topology, fg: int, fc: int, r: Request):
    """Lowest free bits first, no topology: kubelet's degraded admission."""
    if fg.bit_count() < r.gpus or fc.bit_count() < r.cgs:
        return None
    gm = _lowest(fg, r.gpus, t.num_gpus) if r.gpus else 0
    cm = _lowest(fc, r.cgs, t.num_coregroups) if r.cgs else 0
    return gm, cm, achieved_tier(t, gm)


def is_hit(t: Topology, gm: int, cm: int, r: Request) -> bool:
    """Guaranteed NUMA bundles and the best socket tier the size allows."""
    if r.gpus == 0:
        return True
    if r.bundle:
        need = r.cgs // r.gpus
        left = cm
        for g in range(t.num_gpus):
            if not gm >> g & 1:
                continue
            local = left & t.numa_cg[t.numa_of_gpu(g)]
            if local.bit_count() < need:
                return False
            take = need
            for c in range(t.num_coregroups):
                if take == 0:
                    break
                if local >> c & 1:
                    left &= ~(1 << c)
                    take -= 1
    if r.gpus <= t.gpus_per_numa:
        best = 0
    elif r.gpus <= t.gpus_per_numa * t.numa_per_socket:
        best = 1
    else:
        best = 2
    return achieved_tier(t, gm) <= best


def eq1(alpha: float, priority_sum: int, tier: int) -> float:
    prio = 1.0 / priority_sum if priority_sum > 0 else 1.0
    return alpha * prio + (1.0 - alpha) * TIER_SCORES[tier]


class Fleet:
    """Live instances per node; uids are given out in bind order from 0."""

    def __init__(self, topo: Topology, num_nodes: int) -> None:
        self.topo = topo
        self.num_nodes = num_nodes
        self.free = [((1 << topo.num_gpus) - 1, (1 << topo.num_coregroups) - 1)
                     ] * num_nodes
        self.on_node: list[dict[int, tuple]] = [{} for _ in range(num_nodes)]
        self.where: dict[int, int] = {}
        self.next_uid = 0
        self._sig: list[tuple | None] = [None] * num_nodes

    def bind(self, r: Request, node: int, gm: int, cm: int) -> int:
        fg, fc = self.free[node]
        if gm & ~fg or cm & ~fc:
            raise ValueError(f"bind on node {node} overlaps live instances")
        uid = self.next_uid
        self.next_uid += 1
        self.on_node[node][uid] = (r.priority, r.preemptible, gm, cm, r.name)
        self.where[uid] = node
        self.free[node] = (fg & ~gm, fc & ~cm)
        self._sig[node] = None
        return uid

    def evict(self, uid: int) -> tuple:
        node = self.where.pop(uid)
        inst = self.on_node[node].pop(uid)
        fg, fc = self.free[node]
        self.free[node] = (fg | inst[2], fc | inst[3])
        self._sig[node] = None
        return inst

    def signature(self, node: int) -> tuple:
        """(free masks, instances in uid order): all a node's result depends
        on, uids aside."""
        sig = self._sig[node]
        if sig is None:
            insts = self.on_node[node]
            sig = (self.free[node],
                   tuple(insts[u][:4] for u in sorted(insts)))
            self._sig[node] = sig
        return sig

    def copy(self) -> "Fleet":
        return copy.deepcopy(self)


class Reference:
    """Algorithm 1 over a `Fleet`, decision by decision."""

    def __init__(self, topo: Topology, alpha: float) -> None:
        self.topo = topo
        self.alpha = alpha
        self._normal: dict = {}
        self._preempt: dict = {}

    def _node_normal(self, fg: int, fc: int, r: Request):
        key = (fg, fc, r)
        if key not in self._normal:
            p = place(self.topo, fg, fc, r) or place_blind(self.topo, fg, fc, r)
            self._normal[key] = p
        return self._normal[key]

    def _node_preempt(self, sig: tuple, r: Request):
        """Best (score, -k, victim positions) on one node, positions being
        indices into the node's uid-ordered instances; None if infeasible."""
        key = (sig, r)
        if key in self._preempt:
            return self._preempt[key]
        (fg, fc), insts = sig
        victims = [i for i, (prio, pre, _, _) in enumerate(insts)
                   if pre and prio < r.priority]
        # smallest subset size that could free enough by counts alone
        k_min = 0
        if victims:
            max_g = max(insts[i][2].bit_count() for i in victims)
            max_c = max(insts[i][3].bit_count() for i in victims)
            kg = (0 if fg.bit_count() >= r.gpus
                  else -(-(r.gpus - fg.bit_count()) // max(max_g, 1)))
            kc = (0 if fc.bit_count() >= r.cgs
                  else -(-(r.cgs - fc.bit_count()) // max(max_c, 1)))
            k_min = max(kg, kc)
        best = None
        for k in range(k_min, len(victims) + 1):
            for combo in itertools.combinations(victims, k):
                g, c = fg, fc
                psum = 0
                for i in combo:
                    g |= insts[i][2]
                    c |= insts[i][3]
                    psum += insts[i][0]
                tier = best_tier(self.topo, g, c, r)
                if tier == INFEASIBLE:
                    continue
                cand = (eq1(self.alpha, psum, tier), -k,
                        tuple(-i for i in combo))
                if best is None or cand > best:
                    best = cand
            if best is not None:
                break
        self._preempt[key] = best
        return best

    def decide(self, fleet: Fleet, r: Request, allow_normal: bool = True,
               allow_preempt: bool = True) -> tuple:
        """The decision key Algorithm 1 gives for ``r`` on ``fleet``."""
        t = self.topo
        if allow_normal:
            best = None
            for node in range(fleet.num_nodes):
                fg, fc = fleet.free[node]
                if fg.bit_count() < r.gpus or fc.bit_count() < r.cgs:
                    continue
                p = self._node_normal(fg, fc, r)
                if p is None:
                    continue
                key = (p[2], fg.bit_count() - r.gpus, node)
                if best is None or key < best[0]:
                    best = (key, node, p)
            if best is not None:
                _, node, p = best
                return ("placed", node, (), is_hit(t, p[0], p[1], r), p)
        if not allow_preempt:
            return ("rejected", -1, (), False, None)
        win = None
        for node in range(fleet.num_nodes):
            got = self._node_preempt(fleet.signature(node), r)
            if got is None:
                continue
            cand = (got[0], got[1], -node)
            if win is None or cand > win[0]:
                win = (cand, node, got[2])
        if win is None:
            return ("rejected", -1, (), False, None)
        _, node, neg_pos = win
        uids = sorted(fleet.on_node[node])
        victims = tuple(sorted(uids[-i] for i in neg_pos))
        fg, fc = fleet.free[node]
        for u in victims:
            fg |= fleet.on_node[node][u][2]
            fc |= fleet.on_node[node][u][3]
        p = place(t, fg, fc, r) or place_blind(t, fg, fc, r)
        return ("preempted", node, victims, is_hit(t, p[0], p[1], r), p)


def apply(fleet: Fleet, r: Request, key: tuple) -> int | None:
    """Commit a decision key to ``fleet``; returns the new uid (None if
    rejected).  Raises ValueError where the key cannot be committed: a victim
    that is not live, not preemptible or not of lower priority, or masks that
    overlap what stays on the node."""
    kind, node, victims, _, masks = key
    if kind == "rejected":
        return None
    for u in victims:
        if fleet.where.get(u) != node:
            raise ValueError(f"victim {u} is not live on node {node}")
        prio, pre = fleet.on_node[node][u][:2]
        if not pre or prio >= r.priority:
            raise ValueError(f"victim {u} may not be preempted by {r.name}")
    for u in victims:
        fleet.evict(u)
    return fleet.bind(r, node, masks[0], masks[1])
