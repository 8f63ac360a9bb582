"""Pallas TPU kernel for batched victim-subset scoring (paper §3.4 hot loop).

TPU adaptation (DESIGN.md §3): the paper's candidate sourcing walks victim
subsets with branchy CPU code (Table 5: 180-417ms P90 at scale).  Here a
subset is one int32 lane: its freed-GPU/CoreGroup bitmasks.  Per-NUMA
availability is ``popcount(mask & numa_mask)`` — numa masks are compile-time
constants baked into the kernel — and the Eq. 1 score is pure VPU math.  One
grid step scores a (8, 128) tile of subsets from VMEM; a 100k-subset sourcing
wave is a handful of grid steps.

Three kernels share the tier/score math:

* ``topo_score_pallas``        — tier + Eq. 1 score per subset (dense out).
* ``placement_tier_pallas``    — per-NODE placement tier over free masks:
  the VPU mirror of the device placement scorer (`placement_jax`) that the
  fused dispatch chains in front of sourcing (§3.4 Sorting / normal cycle).
* ``topo_score_argmax_pallas`` — same tier math, plus a *per-tile running
  argmax*:
  each grid step also reduces its tile to (smallest feasible subset size,
  best tier, best score, flat index of that winner), so the ``imp_pallas``
  engine evaluates every subset size in ONE dispatch and only scans the
  dense outputs at the winning size.  It also takes a per-lane *filtering
  mask* (``ok``): the scheduler's Guaranteed-Filtering / victim-eligibility
  constraints become VPU lane masking instead of host pre-filtering —
  masked lanes report tier 3 / -inf score and never win the argmax.

Layout: subsets are padded to (rows, 128) int32.  Outputs: tier (0/1/2,
3 = infeasible) and the Eq. 1 score (-inf where infeasible).

``interpret=None`` (the default) runs the Mosaic interpreter iff the JAX
backend is not a TPU (`_interpret_default`): no setting puts a kernel in
interpret mode on a TPU.  ``interpret=False`` compiles for a TPU whatever
the backend (the compile rehearsals in tests/test_tpu_compile.py).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.engines import register_engine
from repro.core.topology import ServerSpec

TIER_VALUES = (1.0, 0.5, 0.1)
ROWS_PER_TILE = 8
LANES = 128
#: k fill value for padding lanes in the argmax kernel (also the "no
#: feasible subset in this tile" sentinel of the per-tile k-min output).
K_INFEASIBLE = np.int32(2**30)


def _interpret_default() -> bool:
    """Mosaic interpreter iff the backend is not a TPU."""
    return jax.default_backend() != "tpu"


@dataclasses.dataclass(frozen=True)
class TopoRequest:
    need_gpus: int
    need_cgs: int
    cgs_per_bundle: int
    alpha: float = 0.5


def _tier_score(g_mask, c_mask, prio, *, spec: ServerSpec, req: TopoRequest):
    """Shared VPU math: (tier int32, Eq. 1 score f32) for one tile."""
    U = spec.num_numa
    S = spec.num_sockets
    shape = g_mask.shape
    zero = jnp.zeros(shape, jnp.int32)
    sock_units = [zero] * S
    sock_cg = [zero] * S
    glob_units = zero
    glob_cg = zero
    numa_ok = jnp.zeros(shape, jnp.bool_)
    for u in range(U):                       # static unroll over NUMA nodes
        ugm = int(spec.numa_gpu_masks[u])    # compile-time constants
        ucm = int(spec.numa_cg_masks[u])
        cnt_gpu = jax.lax.population_count(g_mask & ugm)
        cnt_cg = jax.lax.population_count(c_mask & ucm)
        if req.cgs_per_bundle > 0:
            units = jnp.minimum(cnt_gpu, cnt_cg // req.cgs_per_bundle)
        else:
            units = cnt_gpu
        numa_ok |= (units >= req.need_gpus) & (cnt_cg >= req.need_cgs)
        s = spec.socket_of_numa(u)
        sock_units[s] = sock_units[s] + units
        sock_cg[s] = sock_cg[s] + cnt_cg
        glob_units = glob_units + units
        glob_cg = glob_cg + cnt_cg
    sock_ok = jnp.zeros(shape, jnp.bool_)
    for s in range(S):
        sock_ok |= (sock_units[s] >= req.need_gpus) & (
            sock_cg[s] >= req.need_cgs)
    glob_ok = (glob_units >= req.need_gpus) & (glob_cg >= req.need_cgs)

    tier = jnp.where(numa_ok, 0, jnp.where(sock_ok, 1,
                                           jnp.where(glob_ok, 2, 3)))
    tier = tier.astype(jnp.int32)

    tv = TIER_VALUES + (0.0,)
    topo = jnp.where(tier == 0, tv[0],
                     jnp.where(tier == 1, tv[1],
                               jnp.where(tier == 2, tv[2], tv[3])))
    prio_term = jnp.where(prio > 0,
                          1.0 / jnp.maximum(prio, 1).astype(jnp.float32), 1.0)
    score = req.alpha * prio_term + (1.0 - req.alpha) * topo
    score = jnp.where(tier < 3, score, -jnp.inf).astype(jnp.float32)
    return tier, score


def _kernel(combo_gpu_ref, combo_cg_ref, prio_ref, tier_ref, score_ref, *,
            spec: ServerSpec, req: TopoRequest):
    tier, score = _tier_score(combo_gpu_ref[...], combo_cg_ref[...],
                              prio_ref[...], spec=spec, req=req)
    tier_ref[...] = tier
    score_ref[...] = score


def _argmax_kernel(combo_gpu_ref, combo_cg_ref, prio_ref, k_ref, ok_ref,
                   tier_ref, score_ref, kmin_ref, btier_ref, bscore_ref,
                   bidx_ref, *, spec: ServerSpec, req: TopoRequest):
    """Tier/score tile + filtering mask + per-tile running argmax.

    ``ok`` is the fused filtering input: lanes whose subset violates the
    scheduler's constraints (ineligible victims, filtered-out node) are
    masked to tier 3 / -inf score ON DEVICE, so callers never pre-filter
    subsets host-side.  The reduction implements the IMP selection order
    inside one tile: smallest feasible subset size k first, then
    tier-then-score (lowest tier, highest Eq. 1 score), then lowest flat
    subset index.  Host-side merging of the ``[n_tiles]`` outputs is
    O(tiles) on scalars, so the engine dispatches exactly once per node
    regardless of victim count.
    """
    tier, score = _tier_score(combo_gpu_ref[...], combo_cg_ref[...],
                              prio_ref[...], spec=spec, req=req)
    ok = ok_ref[...] != 0
    tier = jnp.where(ok, tier, 3).astype(jnp.int32)
    score = jnp.where(ok, score, -jnp.inf).astype(jnp.float32)
    tier_ref[...] = tier
    score_ref[...] = score

    k = k_ref[...]
    feas = tier < 3
    big = jnp.int32(K_INFEASIBLE)
    # per-tile scalars go out as lane-dense (1, LANES) rows (a rank-1
    # block of one element per tile is refused by the TPU lowering)
    kmin = jnp.min(jnp.where(feas, k, big))
    kmin_ref[...] = jnp.full(kmin_ref.shape, kmin, jnp.int32)
    sel = feas & (k == kmin)
    tmin = jnp.min(jnp.where(sel, tier, 3))
    btier_ref[...] = jnp.full(btier_ref.shape, tmin, jnp.int32)
    sel &= tier == tmin
    smax = jnp.max(jnp.where(sel, score, -jnp.inf))
    bscore_ref[...] = jnp.full(bscore_ref.shape, smax, jnp.float32)
    sel &= score == smax
    rows, lanes = k.shape
    flat = (jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 0) * lanes
            + jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 1))
    local = jnp.min(jnp.where(sel, flat, big))
    bidx_ref[...] = jnp.full(bidx_ref.shape,
                             pl.program_id(0) * (rows * lanes) + local,
                             jnp.int32)


def _tiled(x, fill, n_pad, tile):
    return jnp.pad(x, [(0, n_pad - x.shape[0])],
                   constant_values=fill).reshape(
        n_pad // tile, ROWS_PER_TILE, LANES)


def topo_score_pallas(
    combo_gpu: jnp.ndarray,      # int32[n] freed-GPU mask per subset
    combo_cg: jnp.ndarray,
    prio: jnp.ndarray,
    spec: ServerSpec,
    req: TopoRequest,
    interpret: bool | None = None,   # None: interpret iff not on a TPU
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (tier int32[n], score f32[n])."""
    if interpret is None:
        interpret = _interpret_default()
    n = combo_gpu.shape[0]
    tile = ROWS_PER_TILE * LANES
    n_pad = -(-n // tile) * tile

    cg2 = _tiled(combo_gpu, 0, n_pad, tile)
    cc2 = _tiled(combo_cg, 0, n_pad, tile)
    pr2 = _tiled(prio, 0, n_pad, tile)

    grid = (n_pad // tile,)
    blk = pl.BlockSpec((None, ROWS_PER_TILE, LANES), lambda i: (i, 0, 0))
    kernel = partial(_kernel, spec=spec, req=req)
    tier, score = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[blk, blk, blk],
        out_specs=[blk, blk],
        out_shape=[
            jax.ShapeDtypeStruct(cg2.shape, jnp.int32),
            jax.ShapeDtypeStruct(cg2.shape, jnp.float32),
        ],
        interpret=interpret,
    )(cg2, cc2, pr2)
    return tier.reshape(-1)[:n], score.reshape(-1)[:n]


def topo_score_argmax_pallas(
    combo_gpu: jnp.ndarray,      # int32[n] freed-GPU mask per subset
    combo_cg: jnp.ndarray,
    prio: jnp.ndarray,
    k: jnp.ndarray,              # int32[n] subset size per lane
    spec: ServerSpec,
    req: TopoRequest,
    interpret: bool | None = None,
    ok: jnp.ndarray | None = None,   # filtering mask per lane (None = all ok)
):
    """Single-dispatch scoring of subsets of EVERY size plus the per-tile
    running argmax.

    ``ok`` is the fused filtering-mask input: lanes with ``ok == 0`` (e.g.
    subsets touching victims the preemptor may not evict, or subsets of a
    node Guaranteed Filtering rejected) are masked infeasible inside the
    kernel instead of being pre-filtered on the host.

    Returns (tier int32[n], score f32[n], kmin int32[T], btier int32[T],
    bscore f32[T], bidx int32[T]) with T = number of (8, 128) grid tiles;
    ``kmin[t] == K_INFEASIBLE`` marks a tile with no feasible subset, and
    ``bidx`` is the *global* flat index of tile t's winner under the
    (k, tier-then-score, index) order.
    """
    if interpret is None:
        interpret = _interpret_default()
    n = combo_gpu.shape[0]
    tile = ROWS_PER_TILE * LANES
    n_pad = -(-n // tile) * tile

    cg2 = _tiled(combo_gpu, 0, n_pad, tile)
    cc2 = _tiled(combo_cg, 0, n_pad, tile)
    pr2 = _tiled(prio, 0, n_pad, tile)
    kk2 = _tiled(k, K_INFEASIBLE, n_pad, tile)
    if ok is None:
        ok = jnp.ones(n, jnp.int32)
    ok2 = _tiled(ok.astype(jnp.int32), 0, n_pad, tile)

    n_tiles = n_pad // tile
    blk = pl.BlockSpec((None, ROWS_PER_TILE, LANES), lambda i: (i, 0, 0))
    scl = pl.BlockSpec((None, 1, LANES), lambda i: (i, 0, 0))
    kernel = partial(_argmax_kernel, spec=spec, req=req)
    tier, score, kmin, btier, bscore, bidx = pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=[blk, blk, blk, blk, blk],
        out_specs=[blk, blk, scl, scl, scl, scl],
        out_shape=[
            jax.ShapeDtypeStruct(cg2.shape, jnp.int32),
            jax.ShapeDtypeStruct(cg2.shape, jnp.float32),
            jax.ShapeDtypeStruct((n_tiles, 1, LANES), jnp.int32),
            jax.ShapeDtypeStruct((n_tiles, 1, LANES), jnp.int32),
            jax.ShapeDtypeStruct((n_tiles, 1, LANES), jnp.float32),
            jax.ShapeDtypeStruct((n_tiles, 1, LANES), jnp.int32),
        ],
        interpret=interpret,
    )(cg2, cc2, pr2, kk2, ok2)
    return (tier.reshape(-1)[:n], score.reshape(-1)[:n], kmin[:, 0, 0],
            btier[:, 0, 0], bscore[:, 0, 0], bidx[:, 0, 0])


def _place_tier_kernel(free_gpu_ref, free_cg_ref, tier_ref, *,
                       spec: ServerSpec, req: TopoRequest):
    """Placement-tier tile: each lane is one NODE's free masks (not a
    victim subset) — the VPU mirror of the normal-cycle / §3.4 tier
    scorer (`repro.core.placement_jax.best_tier_counts`)."""
    tier, _ = _tier_score(free_gpu_ref[...], free_cg_ref[...],
                          jnp.zeros_like(free_gpu_ref[...]),
                          spec=spec, req=req)
    tier_ref[...] = tier


def placement_tier_pallas(
    free_gpu: jnp.ndarray,       # int32[n] free-GPU mask per node
    free_cg: jnp.ndarray,        # int32[n] free-CoreGroup mask per node
    spec: ServerSpec,
    req: TopoRequest,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Per-node placement tier (0/1/2, 3 = infeasible) on the TPU VPU.

    Mirrors the device placement scorer that the fused dispatch chains in
    front of sourcing: popcounts of the per-NUMA free-mask slices with the
    numa masks baked in as compile-time constants.  Bitwise-matching
    ``placement.best_tier`` for the request's ``(need_gpus, need_cgs,
    cgs_per_bundle)`` encoding; the normal-cycle argmin over ``(tier,
    leftover, node)`` is host/XLA reduction work on the dense output.
    """
    if interpret is None:
        interpret = _interpret_default()
    n = free_gpu.shape[0]
    tile = ROWS_PER_TILE * LANES
    n_pad = -(-n // tile) * tile
    fg2 = _tiled(free_gpu, 0, n_pad, tile)
    fc2 = _tiled(free_cg, 0, n_pad, tile)
    blk = pl.BlockSpec((None, ROWS_PER_TILE, LANES), lambda i: (i, 0, 0))
    kernel = partial(_place_tier_kernel, spec=spec, req=req)
    tier = pl.pallas_call(
        kernel,
        grid=(n_pad // tile,),
        in_specs=[blk, blk],
        out_specs=blk,
        out_shape=jax.ShapeDtypeStruct(fg2.shape, jnp.int32),
        interpret=interpret,
    )(fg2, fc2)
    return tier.reshape(-1)[:n]


# ---------------------------------------------------------------------------------
# IMP engine backed by the kernel (scheduler engine "imp_pallas")
# ---------------------------------------------------------------------------------

def _all_size_combos(free_gpu: int, free_cg: int, vg, vc, vp):
    """Every victim subset as its slot-bitmask id: freed masks, priority sum
    and subset size for ids 0..2^m-1 (id 0 = evict nothing)."""
    m = len(vg)
    ids = np.arange(1 << m, dtype=np.int64)
    cg = np.full(ids.shape, free_gpu, np.int64)
    cc = np.full(ids.shape, free_cg, np.int64)
    pr = np.zeros(ids.shape, np.int64)
    kk = np.zeros(ids.shape, np.int64)
    for j in range(m):
        b = (ids >> j) & 1
        cg |= b * int(vg[j])
        cc |= b * int(vc[j])
        pr += b * int(vp[j])
        kk += b
    return ids, cg, cc, pr, kk


@register_engine("imp_pallas")
def flextopo_imp_pallas(cluster, workload, node):
    """Drop-in engine: same semantics as preemption.flextopo_imp, but every
    subset size is evaluated in ONE kernel dispatch — the per-tile running
    argmax locates the smallest feasible size, then candidates are read off
    the dense tier output at that size only.

    Eligible victims are a prefix of the (priority, uid) order, so the
    preemptor-priority filter is a host-side SLICE (never a subset
    enumeration blow-up); the kernel's filtering-mask input (``ok``)
    additionally zeroes any lane whose subset escapes that eligibility —
    the belt-and-braces in-kernel expression of Guaranteed Filtering that
    fused callers with ragged eligibility rely on."""
    from repro.core.cluster import MAX_DENSE_VICTIMS
    from repro.core.scoring import Candidate
    from repro.core.workload import TopoPolicy

    spec = cluster.spec
    victims = cluster.victims_on(node, workload.priority)
    if len(victims) > MAX_DENSE_VICTIMS:
        # 2^m lanes would blow up; the per-node python engine is exact
        from repro.core.preemption import flextopo_imp

        return flextopo_imp(cluster, workload, node)
    free_gpu, free_cg = cluster.free_masks(node)
    need_gpus = workload.gpus_per_instance
    need_cgs = workload.coregroups_per_instance(spec.coregroup_size)
    bundle = workload.numa_policy == TopoPolicy.GUARANTEED
    req = TopoRequest(
        need_gpus=need_gpus, need_cgs=need_cgs,
        cgs_per_bundle=(need_cgs // need_gpus if (bundle and need_gpus) else 0))
    vg = [v.gpu_mask for v in victims]
    vc = [v.cg_mask for v in victims]
    vp = [v.priority for v in victims]
    ids, cg, cc, pr, kk = _all_size_combos(free_gpu, free_cg, vg, vc, vp)
    elig_bits = sum(1 << j for j, v in enumerate(victims)
                    if v.priority < workload.priority)
    ok = (ids & ~np.int64(elig_bits)) == 0
    tier, _, kmin, _, _, _ = topo_score_argmax_pallas(
        jnp.asarray(cg, jnp.int32), jnp.asarray(cc, jnp.int32),
        jnp.asarray(pr, jnp.int32), jnp.asarray(kk, jnp.int32), spec, req,
        ok=jnp.asarray(ok, jnp.int32))
    k_star = int(np.min(np.asarray(kmin)))
    if k_star >= int(K_INFEASIBLE):
        return []
    tier = np.asarray(tier)
    at_min = np.nonzero((tier < 3) & (kk == k_star))[0]
    return [
        Candidate(
            node=node,
            victims=tuple(sorted(
                victims[j].uid for j in range(len(victims))
                if (int(ids[i]) >> j) & 1)),
            tier=int(tier[i]),
            priority_sum=int(pr[i]),
        )
        for i in at_min
    ]
