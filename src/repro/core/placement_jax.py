"""Device-side placement scorer (paper §3.4 Sorting + the normal cycle).

The host ``placement`` module walks NUMA nodes and bit-scans GPU/CoreGroup
masks in python; this module is its bitwise twin as vectorized int32 bit
math, so BOTH cycles of Algorithm 1 can run inside the fused sourcing
dispatch (`repro.core.preemption_jax`):

* `best_tier_counts` / `tier_from_counts_dyn` — tier-0/1/2 bundle
  feasibility from per-NUMA popcounts of the free masks (the Filtering /
  candidate tier math, request as traced scalars);
* `place_core` — the CONCRETE GPU/CoreGroup mask selection of
  ``placement.place``: scope choice (per-NUMA → per-socket → global slices
  of the free masks) by the same best-fit key, then lowest-free-bit
  allocation per NUMA in scope order — bitwise-matching the host;
* `place_blind_core` / `achieved_tier_dev` — ``placement.place_blind`` and
  the committed-tier accounting;
* `normal_cycle_core` — the whole ``TopoScheduler._plan_normal`` sweep:
  per-node placement tier (including the kubelet degraded-admission blind
  fallback for count-feasible but topology-infeasible nodes), the
  ``(tier, leftover, node)`` argmin, and the winner's concrete masks;
* `winner_place` — freed-mask reconstruction + placement for a preemption
  winner, so the sourcing dispatch returns placement masks and the host
  never re-runs ``place()`` on the winning node.

`spec_slices` is the static NUMA/socket slice layout every scorer consumes:
per-NUMA mask columns, the socket one-hot, the scope-membership matrix
(one row per NUMA scope, per socket scope, plus the global scope) and the
prefix masks of the lowest-k-bits selector.  It is cached per `ServerSpec`
and lives on the accelerator next to the resident `DeviceClusterState`.

Host-callable wrappers (`device_best_tier`, `device_place`,
`device_place_blind`) exist for the randomized host-vs-device parity suite
in ``tests/test_placement_device.py``.
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from .placement import INFEASIBLE, Placement
from .topology import ServerSpec

_INT32_MAX = np.int32(2**31 - 1)


@lru_cache(maxsize=None)
def spec_constants(spec: ServerSpec) -> dict[str, jnp.ndarray]:
    """Static mask tensors for one server SKU (shared by every evaluator).

    Built under ``ensure_compile_time_eval``: the first call may happen
    inside a traced ``lax.cond`` branch, and the cache must hold concrete
    arrays, never that branch's tracers."""
    sock_onehot = np.zeros((spec.num_numa, spec.num_sockets), dtype=np.int32)
    for u in range(spec.num_numa):
        sock_onehot[u, spec.socket_of_numa(u)] = 1
    with jax.ensure_compile_time_eval():
        return {
            "numa_gpu_masks": jnp.asarray(spec.numa_gpu_masks),
            "numa_cg_masks": jnp.asarray(spec.numa_cg_masks),
            "sock_onehot": jnp.asarray(sock_onehot),
        }


@dataclasses.dataclass(frozen=True)
class SpecSlices:
    """Static NUMA/socket slice layout of one SKU, device-resident.

    ``scope_mask [n_scopes, U]`` enumerates the placement scopes in the
    host's order — one row per NUMA node, one per socket, one global —
    and ``scope_tier [n_scopes]`` their tier; within a tier, ascending row
    index equals the host's lexicographic numa-list order, so the best-fit
    argmin over ``(leftover, row)`` reproduces ``placement.place``'s scope
    choice exactly.  ``g_bits``/``g_prefix`` (and the cg twins) drive the
    vectorized lowest-k-set-bits selector."""

    numa_gpu: jnp.ndarray      # int32[U]
    numa_cg: jnp.ndarray       # int32[U]
    sock_onehot: jnp.ndarray   # int32[U, S]
    scope_mask: jnp.ndarray    # int32[n_scopes, U]
    scope_tier: jnp.ndarray    # int32[n_scopes]
    g_bits: jnp.ndarray        # int32[num_gpus]        1 << i
    g_prefix: jnp.ndarray      # int32[num_gpus]        (1 << i) - 1
    c_bits: jnp.ndarray        # int32[num_coregroups]
    c_prefix: jnp.ndarray


@lru_cache(maxsize=None)
def spec_slices(spec: ServerSpec) -> SpecSlices:
    consts = spec_constants(spec)
    u_n, s_n = spec.num_numa, spec.num_sockets
    scopes = np.zeros((u_n + s_n + 1, u_n), np.int32)
    tiers = np.zeros(u_n + s_n + 1, np.int32)
    for u in range(u_n):
        scopes[u, u] = 1
    for s in range(s_n):
        for u in range(u_n):
            if spec.socket_of_numa(u) == s:
                scopes[u_n + s, u] = 1
        tiers[u_n + s] = 1
    scopes[-1, :] = 1
    tiers[-1] = 2

    def bits(n):
        b = (np.int64(1) << np.arange(n, dtype=np.int64)).astype(np.int32)
        p = ((np.int64(1) << np.arange(n, dtype=np.int64)) - 1).astype(np.int32)
        return jnp.asarray(b), jnp.asarray(p)

    # concrete arrays even when first called inside a traced cond branch
    # (the lru cache must never hold another trace's tracers)
    with jax.ensure_compile_time_eval():
        g_bits, g_prefix = bits(spec.num_gpus)
        c_bits, c_prefix = bits(spec.num_coregroups)
        return SpecSlices(
            numa_gpu=consts["numa_gpu_masks"],
            numa_cg=consts["numa_cg_masks"],
            sock_onehot=consts["sock_onehot"],
            scope_mask=jnp.asarray(scopes), scope_tier=jnp.asarray(tiers),
            g_bits=g_bits, g_prefix=g_prefix, c_bits=c_bits,
            c_prefix=c_prefix,
        )


def tier_from_counts_dyn(cnt_gpu, cnt_cg, sock_onehot,
                         need_gpus, need_cgs, cgs_per_bundle):
    """Placement tier from per-NUMA availability counts (request traced).

    ``cnt_gpu``/``cnt_cg`` are ``[..., U]``; one compiled program serves
    every preemptor class: ``cgs_per_bundle`` = 0 encodes both "no bundle
    locality" and CPU-only asks (with ``need_gpus`` = 0 the GPU-unit
    comparisons are trivially true, leaving exactly the host's
    CoreGroup-only conditions).
    """
    units = jnp.where(cgs_per_bundle > 0,
                      jnp.minimum(cnt_gpu,
                                  cnt_cg // jnp.maximum(cgs_per_bundle, 1)),
                      cnt_gpu)
    numa_ok = jnp.any((units >= need_gpus) & (cnt_cg >= need_cgs), axis=-1)
    sock_units = units @ sock_onehot
    sock_cg = cnt_cg @ sock_onehot
    sock_ok = jnp.any((sock_units >= need_gpus) & (sock_cg >= need_cgs),
                      axis=-1)
    glob_ok = (jnp.sum(units, axis=-1) >= need_gpus) & (
        jnp.sum(cnt_cg, axis=-1) >= need_cgs)
    return jnp.where(numa_ok, 0, jnp.where(sock_ok, 1,
                                           jnp.where(glob_ok, 2, 3)))


def _lowest_bits_dev(mask, k, bits, prefix):
    """Lowest ``k`` set bits of ``mask`` (broadcasts over leading axes).

    Bit i is selected iff it is set and fewer than ``k`` set bits lie below
    it; when ``mask`` holds fewer than ``k`` bits every set bit is taken
    (callers' remaining-count checks flag the shortfall, mirroring the
    host's ``_lowest_bits`` returning ``None``)."""
    mask = mask[..., None]
    below = jax.lax.population_count(mask & prefix)
    sel = ((mask & bits) != 0) & (below < k[..., None])
    return jnp.sum(jnp.where(sel, bits, 0), axis=-1)


def achieved_tier_dev(gpu_mask, sl: SpecSlices):
    """``placement.achieved_tier`` (broadcasts over leading axes)."""
    touched = (gpu_mask[..., None] & sl.numa_gpu) != 0          # [..., U]
    n_numa = jnp.sum(touched, axis=-1)
    n_sock = jnp.sum((touched.astype(jnp.int32) @ sl.sock_onehot) > 0,
                     axis=-1)
    return jnp.where(gpu_mask == 0, 0,
                     jnp.where(n_numa <= 1, 0,
                               jnp.where(n_sock <= 1, 1, 2))).astype(jnp.int32)


def best_tier_counts(free_gpu, free_cg, ng, nc, cpb, sl: SpecSlices):
    """Per-NUMA popcounts + tier for free masks of any leading shape."""
    cnt_g = jax.lax.population_count(free_gpu[..., None] & sl.numa_gpu)
    cnt_c = jax.lax.population_count(free_cg[..., None] & sl.numa_cg)
    tier = tier_from_counts_dyn(cnt_g, cnt_c, sl.sock_onehot, ng, nc, cpb)
    return tier.astype(jnp.int32), cnt_g, cnt_c


def place_core(free_gpu, free_cg, ng, nc, cpb, *, spec: ServerSpec):
    """``placement.place`` for ONE node as scalar bit math.

    Returns ``(ok bool[], tier int32[], gpu_mask int32[], cg_mask
    int32[])``; bitwise-matching the host: same best-fit scope choice
    (least leftover bundle capacity, then lowest scope), same
    lowest-free-bit allocation per NUMA in scope index order, same
    leftover-CoreGroup sweep.
    """
    sl = spec_slices(spec)
    u_n = spec.num_numa
    tier, cnt_g, cnt_c = best_tier_counts(free_gpu, free_cg, ng, nc, cpb, sl)
    units_u = jnp.where(cpb > 0,
                        jnp.minimum(cnt_g, cnt_c // jnp.maximum(cpb, 1)),
                        cnt_g)                                   # [U]
    s_units = sl.scope_mask @ units_u                            # [n_scopes]
    s_cg = sl.scope_mask @ cnt_c
    feas = (s_units >= ng) & (s_cg >= nc) & (sl.scope_tier == tier)
    n_scopes = sl.scope_mask.shape[0]
    key = jnp.where(feas,
                    (s_units - ng) * n_scopes
                    + jnp.arange(n_scopes, dtype=jnp.int32), _INT32_MAX)
    si = jnp.argmin(key)
    member = sl.scope_mask[si]                                   # [U]
    ok = (tier < 3) & jnp.any(feas)

    gpu_mask = jnp.int32(0)
    cg_mask = jnp.int32(0)
    rem_g = jnp.int32(ng)
    rem_c = jnp.int32(nc)
    for u in range(u_n):                 # static unroll over NUMA nodes
        u_free_g = free_gpu & sl.numa_gpu[u]
        u_free_c = free_cg & sl.numa_cg[u]
        take = jnp.minimum(rem_g, units_u[u]) * member[u]
        gpu_mask = gpu_mask | _lowest_bits_dev(u_free_g, take,
                                               sl.g_bits, sl.g_prefix)
        rem_g = rem_g - take
        c_take = jnp.minimum(take * cpb, rem_c)
        cg_mask = cg_mask | _lowest_bits_dev(u_free_c, c_take,
                                             sl.c_bits, sl.c_prefix)
        rem_c = rem_c - c_take
    for u in range(u_n):                 # leftover CoreGroups, scope order
        avail = free_cg & sl.numa_cg[u] & ~cg_mask
        take = jnp.minimum(jax.lax.population_count(avail), rem_c) * member[u]
        cg_mask = cg_mask | _lowest_bits_dev(avail, take,
                                             sl.c_bits, sl.c_prefix)
        rem_c = rem_c - take
    ok = ok & (rem_g == 0) & (rem_c == 0)
    return ok, tier, gpu_mask, cg_mask


def place_blind_core(free_gpu, free_cg, ng, nc, *, spec: ServerSpec):
    """``placement.place_blind`` (broadcasts over leading axes)."""
    sl = spec_slices(spec)
    ok = (jax.lax.population_count(free_gpu) >= ng) & (
        jax.lax.population_count(free_cg) >= nc)
    k_g = jnp.broadcast_to(jnp.int32(ng), jnp.shape(free_gpu))
    k_c = jnp.broadcast_to(jnp.int32(nc), jnp.shape(free_cg))
    gpu_mask = _lowest_bits_dev(free_gpu, k_g, sl.g_bits, sl.g_prefix)
    cg_mask = _lowest_bits_dev(free_cg, k_c, sl.c_bits, sl.c_prefix)
    return ok, achieved_tier_dev(gpu_mask, sl), gpu_mask, cg_mask


def normal_cycle_core(nodestate, ng, nc, cpb, *, spec: ServerSpec):
    """``TopoScheduler._plan_normal`` as one device sweep.

    Per node: count pre-screen, placement tier (topology-feasible nodes
    place at ``best_tier``; count-feasible but topology-infeasible nodes
    admit DEGRADED via the blind allocator at its achieved tier — the
    kubelet best-effort branch), then the host's exact ``(tier, leftover,
    node)`` argmin and the winner's concrete masks via `place_core` /
    `place_blind_core`.

    ``nodestate`` rows with node_id = INT32_MAX (pad sentinels) never win.
    Returns int32[5]: (found, node, tier, gpu_mask, cg_mask).
    """
    from .cluster import NS_FREE_CG, NS_FREE_GPU, NS_NODE_ID

    sl = spec_slices(spec)
    free_g = nodestate[NS_FREE_GPU]
    free_c = nodestate[NS_FREE_CG]
    node_ids = nodestate[NS_NODE_ID]
    cnt_g = jax.lax.population_count(free_g)
    cnt_ok = (cnt_g >= ng) & (jax.lax.population_count(free_c) >= nc) & (
        node_ids < _INT32_MAX)
    tier, _, _ = best_tier_counts(free_g, free_c, ng, nc, cpb, sl)   # [N]
    placeable = tier < 3
    b_ok, b_tier, b_g, b_c = place_blind_core(free_g, free_c, ng, nc,
                                              spec=spec)
    eff_tier = jnp.where(placeable, tier, b_tier)
    leftover = cnt_g - ng
    big = _INT32_MAX
    t = jnp.where(cnt_ok, eff_tier, big)
    sel = cnt_ok & (eff_tier == jnp.min(t))
    l = jnp.where(sel, leftover, big)
    sel = sel & (leftover == jnp.min(l))
    nid = jnp.where(sel, node_ids, big)
    row = jnp.argmin(nid)
    found = jnp.any(cnt_ok)
    # the winner's column in ONE dynamic slice: XLA:CPU's fusion emitter
    # miscompiled the per-vector ``x[row]`` reads feeding this stack when
    # the request was a compile-time constant (wrong gpu_mask)
    win = jax.lax.dynamic_index_in_dim(
        jnp.stack([free_g, free_c, node_ids, placeable.astype(jnp.int32),
                   b_tier, b_g, b_c]), row, axis=1, keepdims=False)
    w_fg, w_fc, w_id, w_placeable, w_bt, w_bg, w_bc = (
        win[i] for i in range(7))
    p_ok, p_tier, p_g, p_c = place_core(w_fg, w_fc, ng, nc, cpb, spec=spec)
    use_place = (w_placeable > 0) & p_ok
    return jnp.stack([
        found.astype(jnp.int32),
        w_id,
        jnp.where(use_place, p_tier, w_bt),
        jnp.where(use_place, p_g, w_bg),
        jnp.where(use_place, p_c, w_bc),
    ])


def winner_place(win, free_gpu, free_cg, victim_gpu, victim_cg,
                 ng, nc, cpb, *, spec: ServerSpec):
    """Placement masks for a preemption winner, inside the dispatch.

    ``win`` is the `int32[7]` Eq. 2 argmax vector (found, row, tier,
    combo_id, prio_sum, k, n_candidates); the winner's freed masks are
    reconstructed from its node row and combo bits (victim masks of one
    node are disjoint, so the fold is a dot product) and placed with
    `place_core` — the host decodes masks instead of re-running
    ``place()``.  Returns int32[9]: ``win`` + (gpu_mask, cg_mask).
    """
    row = win[1]
    combo = win[3]
    cap = victim_gpu.shape[-1]
    bits = ((combo >> jnp.arange(cap, dtype=jnp.int32)) & 1)     # [cap]
    freed_g = free_gpu[row] | jnp.sum(bits * victim_gpu[row])
    freed_c = free_cg[row] | jnp.sum(bits * victim_cg[row])
    _, _, p_g, p_c = place_core(freed_g, freed_c, ng, nc, cpb, spec=spec)
    return jnp.concatenate([win, jnp.stack([p_g, p_c])])


# ---------------------------------------------------------------------------------
# Host-callable wrappers (parity oracle surface for the tests)
# ---------------------------------------------------------------------------------

def _req_of(spec: ServerSpec, need_gpus: int, need_cgs: int,
            bundle_locality: bool) -> tuple[int, int, int]:
    cpb = need_cgs // need_gpus if (bundle_locality and need_gpus) else 0
    return need_gpus, need_cgs, cpb


@lru_cache(maxsize=None)
def _best_tier_jit(spec: ServerSpec):
    sl = spec_slices(spec)

    def f(fg, fc, ng, nc, cpb):
        tier, _, _ = best_tier_counts(fg, fc, ng, nc, cpb, sl)
        return tier

    return obs.program("best_tier", f)


@lru_cache(maxsize=None)
def _place_jit(spec: ServerSpec):
    def f(fg, fc, ng, nc, cpb):
        ok, tier, g, c = place_core(fg, fc, ng, nc, cpb, spec=spec)
        return jnp.stack([ok.astype(jnp.int32), tier, g, c])

    return obs.program("place", f)


@lru_cache(maxsize=None)
def _place_blind_jit(spec: ServerSpec):
    def f(fg, fc, ng, nc):
        ok, tier, g, c = place_blind_core(fg, fc, ng, nc, spec=spec)
        return jnp.stack([ok.astype(jnp.int32), tier, g, c])

    return obs.program("place_blind", f)


def _i32(x: int) -> jnp.ndarray:
    return jnp.int32(np.int64(x).astype(np.int32))


def device_best_tier(spec: ServerSpec, free_gpu: int, free_cg: int,
                     need_gpus: int, need_cgs: int,
                     bundle_locality: bool = True) -> int:
    """Host-callable `best_tier` twin (returns `placement.INFEASIBLE`=3)."""
    ng, nc, cpb = _req_of(spec, need_gpus, need_cgs, bundle_locality)
    tier = _best_tier_jit(spec)(_i32(free_gpu), _i32(free_cg),
                                jnp.int32(ng), jnp.int32(nc), jnp.int32(cpb))
    return int(tier)


def _decode_placement(vec) -> Placement | None:
    ok, tier, g, c = (int(x) for x in np.asarray(vec))
    if not ok or tier >= INFEASIBLE:
        return None
    return Placement(gpu_mask=g & 0xFFFFFFFF, cg_mask=c & 0xFFFFFFFF,
                     tier=tier)


def device_place(spec: ServerSpec, free_gpu: int, free_cg: int,
                 need_gpus: int, need_cgs: int,
                 bundle_locality: bool = True) -> Placement | None:
    """Host-callable `place` twin (bitwise-identical masks)."""
    ng, nc, cpb = _req_of(spec, need_gpus, need_cgs, bundle_locality)
    return _decode_placement(_place_jit(spec)(
        _i32(free_gpu), _i32(free_cg),
        jnp.int32(ng), jnp.int32(nc), jnp.int32(cpb)))


def device_place_blind(spec: ServerSpec, free_gpu: int, free_cg: int,
                       need_gpus: int, need_cgs: int) -> Placement | None:
    """Host-callable `place_blind` twin."""
    vec = _place_blind_jit(spec)(_i32(free_gpu), _i32(free_cg),
                                 jnp.int32(need_gpus), jnp.int32(need_cgs))
    ok, tier, g, c = (int(x) for x in np.asarray(vec))
    if not ok:
        return None
    return Placement(gpu_mask=g & 0xFFFFFFFF, cg_mask=c & 0xFFFFFFFF,
                     tier=tier)
