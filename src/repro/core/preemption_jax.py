"""Vectorized victim-subset evaluation (TPU adaptation of the paper's hot loop).

The paper's candidate sourcing is a branchy per-subset CPU loop (Table 5: up
to 417 ms P90).  Here every victim subset is evaluated in a single dense
sweep: victim resources are int32 bitmasks, feasibility is
``popcount(freed & numa_mask)`` lane math, and the subset axis is a vector
axis.  Every evaluator factory takes the device mesh of the resident state
first (``None``: one device); over a mesh it builds the same program with
explicit shardings (the ``imp_sharded`` engine).

* ``imp_batched`` (default, *fused*): ONE jit dispatch over the cluster's
  DEVICE-RESIDENT state (`DeviceClusterState`) runs Guaranteed Filtering
  (full-drain popcount feasibility), every victim subset of every size — a
  subset is its slot-bitmask id, so ``k`` is just ``popcount(id)`` — and the
  per-node smallest-feasible-``k`` plus the global Eq. 2 argmax.  No node
  list crosses host→device: the scheduler skips its host Filtering loop
  entirely (``fused_filter``), copy-on-write `ClusterView` deltas are
  overlaid inside the dispatch as scattered patch rows, and only the
  winner's indices AND its concrete placement masks (an
  ``int32[WIN_FIELDS]``, placed by the `placement_jax` §3.4 scorer) cross
  back.  Nodes with more than `NARROW_M` eligible victims are gated out
  in-device and re-dispatched through chunked 2^16-subset programs fed
  device-side gather indices.

The engine also registers ``fused_place``: `plan_fused` chains the NORMAL
scheduling cycle (`placement_jax.normal_cycle_core` — per-node placement
tiers, the host's exact ``(tier, leftover, node)`` argmin, and the winner's
masks) in front of the preemptive chain under ``lax.cond``, so the whole of
Algorithm 1 — both cycles, Filtering, Sorting, Eq. 2 AND placement — is one
device program and one small readback (`plan_evaluator`), with the subset
sweep never executed when the normal cycle succeeds.

``plan_batch`` requests ride a `BatchSourcingSession`: one *vmapped*
dispatch evaluates ALL requests' per-node class winners against one
snapshot, and the sequential planned-eviction semantics are preserved by
masking each plan's delta nodes out of the precomputed tensors on device
and re-sourcing only those rows against the view.

Tier convention matches ``placement.best_tier``:
0 = single NUMA, 1 = single socket, 2 = cross-socket, 3 = infeasible.
"""
from __future__ import annotations

import dataclasses
from collections import Counter
from functools import lru_cache
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import obs
from .cluster import (DRAIN_FIELDS, MAX_DENSE_VICTIMS, NODE_FIELDS,
                      NS_FREE_CG, NS_FREE_GPU, NS_NEXT_PRIO, NS_NODE_ID,
                      NS_OVERFLOW, VF_CG, VF_GPU, VF_PRIO, VF_RANK,
                      VF_STORED, VICTIM_FIELDS, Cluster, DeviceClusterState,
                      VictimRow, ViewDelta, _pad_pow2, apply_rows,
                      encode_row, flatten_rows, mesh_program, pack_bits,
                      pack_context_rows, pack_rows, pad_idx, row_width,
                      unflatten_rows, unpack_bits)
from .engines import register_engine
from .placement import Placement
from .placement_jax import (normal_cycle_core, spec_constants,
                            tier_from_counts_dyn, winner_place)
from .scoring import DEFAULT_ALPHA, TIER_SCORES, Candidate, select_best
from .topology import ServerSpec
from .workload import TopoPolicy, WorkloadSpec


def _overflow_candidates(cluster, workload: WorkloadSpec, nodes: list[int],
                         fallbacks: Counter | None = None) -> list[Candidate]:
    """Per-node host IMP for rows the device programs cannot hold;
    ``fallbacks`` (a `DeviceClusterState.fallbacks`) counts them."""
    from .preemption import flextopo_imp

    if fallbacks is not None:
        fallbacks["host_rows"] += len(nodes)
    out: list[Candidate] = []
    for node in nodes:
        out.extend(flextopo_imp(cluster, workload, node))
    return out


# ---------------------------------------------------------------------------------
# Fused single-dispatch sourcing (engine "imp_batched")
# ---------------------------------------------------------------------------------
#
# A victim subset is its slot-bitmask id c in [0, 2^m): member slots are the
# set bits of c and the subset size is popcount(c), so every size k=0..m is
# evaluated in ONE device program with no ragged tables.  The same program
# runs Guaranteed Filtering first — the fully-drained masks kept resident in
# `DeviceClusterState.drain` go through the identical popcount tier math, and
# nodes whose drain state is infeasible contribute no candidates (which is
# exactly the host filter's semantics: a subset is feasible only if the
# full drain is) — then reduces to the final Eq. 2 winner on device,
# reproducing `scoring.select_best`'s ordering:
#
#   maximize  (Eq. 1 score, fewer victims, lower node id,
#              lexicographically smallest sorted victim-uid tuple)
#
# The uid tie-break uses the rank trick: slot j's uid-rank r_j (from the
# SourcingContext mirror) contributes bit (cap-1-r_j) to a combo "uid mask",
# and for equal-size subsets of one node, larger uid mask ==
# lexicographically smaller sorted uid tuple.  Scores are compared in f32 on
# device with an exact integer priority-sum refinement between ties, which
# matches the host's f64 ordering whenever distinct candidate scores are at
# least a few f32 ulps apart — true for realistic priority scales (the
# per-class gap is alpha*|1/p1 - 1/p2| >= alpha/p^2 which stays above f32
# resolution for priorities up to tens of thousands); the host engine
# ``imp`` keeps the exact f64 semantics for adversarial inputs.
#
# The preemptor's resource ask is DYNAMIC (traced int32 scalars), so one
# compiled program serves every workload class — jit variants are keyed only
# by (spec, victim-slot width m, patch bucket p).

_INT32_MAX = np.int32(2**31 - 1)

#: victim-slot width of the resident single-dispatch program; nodes with
#: more eligible victims are gated out in-device and re-dispatched wide
NARROW_M = 8


class ClassWinners(NamedTuple):
    """Per-(node, tier) class-winner tensors produced by `_fused_class_core`.

    ``anyc[N, 3]`` marks classes holding at least one min-k feasible subset;
    ``cb``/``pp``/``um`` are the class winner's combo id, priority sum, and
    uid-rank mask; ``k_node[N]`` is each node's smallest feasible subset
    size and ``cnt[N]`` its feasible min-k subset count (the number of
    candidates ``imp`` lists)."""

    anyc: jnp.ndarray
    cb: jnp.ndarray
    pp: jnp.ndarray
    um: jnp.ndarray
    k_node: jnp.ndarray
    cnt: jnp.ndarray


def _fused_class_core(
    nodestate: jnp.ndarray,  # int32[NODE_FIELDS, N]
    victims: jnp.ndarray,    # int32[VICTIM_FIELDS, N, >= m]
    drain: jnp.ndarray,      # int32[DRAIN_FIELDS, N] fully-drained masks
    thresh: jnp.ndarray,     # int32[]  preemptor priority
    need_gpus: jnp.ndarray,  # int32[]
    need_cgs: jnp.ndarray,   # int32[]
    cgs_per_bundle: jnp.ndarray,  # int32[] (0 = no bundle locality)
    alpha: jnp.ndarray,      # f32[]    Eq. 1 weight
    *,
    spec: ServerSpec,
    m: int,
    narrow_gate: bool,
) -> ClassWinners:
    """Filtering + all-2^m-subset evaluation + per-(node, tier) reduction.

    Guaranteed Filtering runs first on the resident ``drain`` masks — the
    same popcount tier math over the fully-drained state; filtered-out
    nodes contribute nothing (their subsets could never be feasible, so
    this is bitwise-identical to the scheduler's host filter).  With
    ``narrow_gate`` the program additionally gates out rows whose ELIGIBLE
    victims (priority < preemptor, always a prefix of the sorted row)
    exceed ``m`` slots, and truncated rows whose eligible victims extend
    past the stored prefix — the host re-dispatches those wide/overflow.

    Victim masks of one node are pairwise disjoint and disjoint from the
    free mask (the allocator guarantees it), so every per-subset fold —
    freed-GPU/CG masks, priority sum, and the uid-rank tie-break mask — is
    a single int32 matmul against the static subset-membership bit table.
    Rows with node_id = INT32_MAX (gather/pad sentinels) can never win.
    """
    free_gpu = nodestate[NS_FREE_GPU]
    free_cg = nodestate[NS_FREE_CG]
    node_ids = nodestate[NS_NODE_ID]
    vp_full = victims[VF_PRIO]
    stored_full = victims[VF_STORED] != 0
    vg = victims[VF_GPU, :, :m]
    vc = victims[VF_CG, :, :m]
    vp = vp_full[:, :m]
    rank = victims[VF_RANK, :, :m]
    stored = stored_full[:, :m]

    consts = spec_constants(spec)
    numa_g = consts["numa_gpu_masks"]
    numa_c = consts["numa_cg_masks"]
    sock_onehot = consts["sock_onehot"]

    # ---- Guaranteed Filtering, fused: popcounts over the drain masks ------------
    dcnt_g = jax.lax.population_count(drain[0][:, None] & numa_g[None, :])
    dcnt_c = jax.lax.population_count(drain[1][:, None] & numa_c[None, :])
    drain_tier = tier_from_counts_dyn(dcnt_g, dcnt_c, sock_onehot,
                                      need_gpus, need_cgs, cgs_per_bundle)
    node_ok = (drain_tier < 3) & (node_ids < _INT32_MAX)
    if narrow_gate:
        elig_full = jnp.sum((stored_full & (vp_full < thresh))
                            .astype(jnp.int32), axis=1)
        overflow = nodestate[NS_OVERFLOW] != 0
        next_prio = nodestate[NS_NEXT_PRIO]
        node_ok &= (elig_full <= m) & ~(overflow & (next_prio < thresh))

    n_comb = 1 << m
    cids = jnp.arange(n_comb, dtype=jnp.int32)
    kk = jax.lax.population_count(cids)                       # [n_comb]
    bits = ((cids[None, :] >> jnp.arange(m, dtype=jnp.int32)[:, None])
            & 1)                                              # [m, n_comb]

    # victims valid under this preemptor: stored & strictly lower priority
    valid_slot = stored & (vp < thresh)                       # [N, m]
    slot_bits = jnp.left_shift(
        jnp.int32(1), jnp.arange(m, dtype=jnp.int32))         # [m]
    valid_mask = valid_slot.astype(jnp.int32) @ slot_bits      # [N]
    combo_ok = ((cids[None, :] & ~valid_mask[:, None]) == 0    # [N, n_comb]
                ) & node_ok[:, None]

    # all per-subset folds in one [4, N, m] @ [m, n_comb] contraction.
    # rank bits use the full cap width: truncated rows carry uid-ranks over
    # the whole stored prefix, which can exceed the sliced bucket m.
    rankbit = jnp.left_shift(jnp.int32(1), MAX_DENSE_VICTIMS - 1 - rank)
    payload = jnp.stack([vg, vc, vp, rankbit])                 # [4, N, m]
    sums = jax.lax.dot_general(payload, bits,
                               (((2,), (0,)), ((), ())))       # [4, N, n_comb]
    combo_gpu = free_gpu[:, None] + sums[0]    # disjoint masks: sum == OR
    combo_cg = free_cg[:, None] + sums[1]
    prio_sum = sums[2]
    umask = sums[3]

    # per-NUMA availability: popcount(freed & numa_mask) -> [N, n_comb, U]
    cnt_gpu = jax.lax.population_count(
        combo_gpu[:, :, None] & numa_g[None, None, :])
    cnt_cg = jax.lax.population_count(
        combo_cg[:, :, None] & numa_c[None, None, :])
    tier = tier_from_counts_dyn(cnt_gpu, cnt_cg, sock_onehot,
                                need_gpus, need_cgs, cgs_per_bundle)
    tier = jnp.where(combo_ok, tier, 3).astype(jnp.int32)

    # ---- per-node smallest feasible k (IMP early stop, on device) ---------------
    feasible = tier < 3
    big_k = jnp.int32(m + 1)
    k_node = jnp.min(jnp.where(feasible, kk[None, :], big_k), axis=1)   # [N]
    atmin = feasible & (kk[None, :] == k_node[:, None])
    cnt = jnp.sum(atmin.astype(jnp.int32), axis=1)             # [N]

    # ---- per-(node, tier) winner via exact integer keys -------------------------
    # within one node all candidates share k, so the Eq. 2 order inside a
    # (node, tier) class is: smaller priority sum (when alpha > 0), then the
    # uid tie-break (always) — tensorized over the three tier classes.
    p_eff = jnp.where(alpha > 0, prio_sum, 0)
    big_p = jnp.int32(_INT32_MAX)
    t3 = jnp.arange(3, dtype=jnp.int32)
    sel = atmin[:, :, None] & (tier[:, :, None] == t3)         # [N, n_comb, 3]
    anyc = jnp.any(sel, axis=1)                                # [N, 3]
    pmin = jnp.min(jnp.where(sel, p_eff[:, :, None], big_p), axis=1)
    sel = sel & (p_eff[:, :, None] == pmin[:, None, :])
    umax = jnp.max(jnp.where(sel, umask[:, :, None], -1), axis=1)
    sel = sel & (umask[:, :, None] == umax[:, None, :])
    cb = jnp.argmax(sel, axis=1).astype(jnp.int32)             # [N, 3]
    pp = jnp.take_along_axis(prio_sum, cb, axis=1)             # [N, 3]
    um = jnp.take_along_axis(umask, cb, axis=1)
    return ClassWinners(anyc=anyc, cb=cb, pp=pp, um=um, k_node=k_node,
                        cnt=cnt)


def _fused_argmax_core(node_ids, cls: ClassWinners, alpha):
    """Global Eq. 2 argmax over the <= 3N class winners.

    Returns int32[7]: (found, row, tier, combo_id, prio_sum, k,
    n_candidates): ``row`` indexes the class tensors' node axis and
    ``combo_id``'s set bits are the winning victim slots.
    """
    anyc, cb, pp, um, k_node, cnt = cls
    tier_vals = jnp.asarray(tuple(TIER_SCORES), jnp.float32)
    prio_term = jnp.where(pp > 0,
                          1.0 / jnp.maximum(pp, 1).astype(jnp.float32), 1.0)
    score = alpha * prio_term + (1.0 - alpha) * tier_vals[None, :]
    score = jnp.where(anyc, score, -jnp.inf)
    sel = anyc & (score == jnp.max(score))
    # Exact refinement between f32 score ties, then the host tie-break
    # chain: fewer victims, lower node, uid order.  When every survivor
    # shares one tier, an f32 tie with distinct priority sums means f32
    # merely conflated scores f64 distinguishes — refine by lower priority
    # sum (the f64 order).  Survivors from DIFFERENT tiers are treated as a
    # genuine Eq. 1 tie and skip the refinement so the victim-count break
    # applies first, as in `select_best`.  The one case left behind is a
    # cross-tier pair whose f64 scores differ by less than f32 resolution —
    # that needs single-digit priority sums; the host engine ``imp`` keeps
    # exact f64 semantics for such adversarial inputs.
    big_p = jnp.int32(_INT32_MAX)
    t3 = jnp.arange(3, dtype=jnp.int32)
    ppe = jnp.where(alpha > 0, pp, 0)
    tcol = jnp.broadcast_to(t3[None, :], sel.shape)
    same_tier = (jnp.min(jnp.where(sel, tcol, 3))
                 == jnp.max(jnp.where(sel, tcol, -1)))
    ppe_key = jnp.where(same_tier, ppe, 0)
    sel = sel & (ppe_key == jnp.min(jnp.where(sel, ppe_key, big_p)))
    kn = jnp.broadcast_to(k_node[:, None], sel.shape)
    sel = sel & (kn == jnp.min(jnp.where(sel, kn, big_p)))
    nid = jnp.broadcast_to(node_ids[:, None], sel.shape)
    sel = sel & (nid == jnp.min(jnp.where(sel, nid, big_p)))
    sel = sel & (um == jnp.max(jnp.where(sel, um, -1)))
    flat = jnp.argmax(sel.reshape(-1)).astype(jnp.int32)
    row = flat // 3
    return jnp.stack([
        jnp.any(anyc).astype(jnp.int32),     # found
        row,                                 # node-axis row of the winner
        flat % 3,                            # tier
        cb.reshape(-1)[flat],                # combo id (victim-slot bitmask)
        pp.reshape(-1)[flat],                # priority sum
        k_node[row],                         # subset size
        jnp.sum(cnt),                        # n_candidates
    ])


def _overlay(nodestate, victims, drain, pidx, pbuf):
    """Apply flattened view-delta patch rows as a device-side overlay
    (the traced twin of the resident-state scatter)."""
    with jax.named_scope("overlay"):
        return apply_rows(nodestate, victims, drain, pidx, pbuf)


def _overlay_ns(nodestate, idx, buf):
    """Overlay patch rows onto the nodestate tensor alone (the normal-cycle
    evaluator needs free masks only, not victim/drain rows)."""
    cap = (buf.shape[1] - NODE_FIELDS - DRAIN_FIELDS) // VICTIM_FIELDS
    with jax.named_scope("overlay"):
        a, _, _ = unflatten_rows(buf, cap)
        return nodestate.at[:, idx].set(a, mode="drop")


def _split_aux(aux, p: int, s: int, width: int):
    """Static slices of a plan's packed argument buffer (`_plan_aux`):
    ``(pidx int32[p], sel int32[s], pbuf int32[p, width], tail)`` — the
    patch indices, the gather or forced-rep indices, the flattened patch
    rows, and what follows them (the shortlist's rep-mask words)."""
    o = p + s
    e = o + p * width
    return aux[:p], aux[p:o], aux[o:e].reshape(p, width), aux[e:]


def _rep_mask(words, n_rows: int, rep_sh=None):
    """The rep mask unpacked from its `pack_bits` words, laid out by
    ``rep_sh`` (`_rep_sharding`) when given."""
    rep = unpack_bits(words, n_rows)
    if rep_sh is not None:
        rep = jax.lax.with_sharding_constraint(rep, rep_sh)
    return rep


#: width of a decoded preemption winner: the int32[7] Eq. 2 argmax vector
#: plus the winner's (gpu_mask, cg_mask) placement from the device scorer
WIN_FIELDS = 9


def _sorting_winner(nodestate, victims, drain, gidx,
                    thresh, ng, nc, cpb, alpha, *, spec, m, g):
    """Filtering → subset evaluation → Eq. 2 argmax → winner placement.

    Runs over the (already-overlaid) resident tensors at slot width ``m``
    plus a gathered `NARROW_M`-wide section over the ``g`` mid-tier rows
    named by ``gidx``, then places the winner with the §3.4 device scorer
    (`placement_jax.winner_place`) so the host decodes concrete
    GPU/CoreGroup masks instead of re-running ``place()``.  Returns
    int32[`WIN_FIELDS`]."""
    with jax.named_scope("exact_sweep"):
        cls = _fused_class_core(nodestate, victims, drain, thresh, ng, nc,
                                cpb, alpha, spec=spec, m=m, narrow_gate=True)
        node_ids = nodestate[NS_NODE_ID]
        fg_cat = nodestate[NS_FREE_GPU]
        fc_cat = nodestate[NS_FREE_CG]
        vg_cat = victims[VF_GPU]
        vc_cat = victims[VF_CG]
        if g:
            ns = jnp.take(nodestate, gidx, axis=1, mode="fill", fill_value=0)
            vv = jnp.take(victims, gidx, axis=1, mode="fill", fill_value=0)
            dd = jnp.take(drain, gidx, axis=1, mode="fill", fill_value=0)
            ns = ns.at[NS_NODE_ID].set(gidx)
            cls_g = _fused_class_core(ns, vv, dd, thresh, ng, nc, cpb,
                                      alpha, spec=spec, m=NARROW_M,
                                      narrow_gate=False)
            cls = ClassWinners(*(jnp.concatenate([a, b])
                                 for a, b in zip(cls, cls_g)))
            node_ids = jnp.concatenate([node_ids, ns[NS_NODE_ID]])
            fg_cat = jnp.concatenate([fg_cat, ns[NS_FREE_GPU]])
            fc_cat = jnp.concatenate([fc_cat, ns[NS_FREE_CG]])
            vg_cat = jnp.concatenate([vg_cat, vv[VF_GPU]])
            vc_cat = jnp.concatenate([vc_cat, vv[VF_CG]])
        win = _fused_argmax_core(node_ids, cls, alpha)
    with jax.named_scope("winner_place"):
        return winner_place(win, fg_cat, fc_cat, vg_cat, vc_cat, ng, nc,
                            cpb, spec=spec)


def _plan_pipeline(nodestate, victims, drain, aux,
                   thresh, ng, nc, cpb, alpha, *, spec, m, p, g):
    """The preemption phase as one traced pipeline: overlay ``p`` patch
    rows (view deltas + unflushed dirty rows), then `_sorting_winner` —
    a single dispatch and a single int32[`WIN_FIELDS`] readback.  ``aux``
    is the plan's packed ``[pidx | gidx | pbuf]`` buffer."""
    pidx, gidx, pbuf, _ = _split_aux(aux, p, g, row_width(victims.shape[2]))
    if p:
        nodestate, victims, drain = _overlay(nodestate, victims, drain,
                                             pidx, pbuf)
    return _sorting_winner(nodestate, victims, drain, gidx,
                           thresh, ng, nc, cpb, alpha, spec=spec, m=m, g=g)


def _plan2_pipeline(nodestate, victims, drain, aux,
                    thresh, ng, nc, cpb, alpha, *, spec, m, p, g):
    """BOTH cycles of Algorithm 1 as one traced program.

    Overlay ``p`` patch rows, then the normal-cycle argmin + winner
    placement (`placement_jax.normal_cycle_core`) over ALL nodes; the
    preemptive `_sorting_winner` chain runs under ``lax.cond`` ONLY when
    the normal cycle found nothing, so the common no-preemption case pays
    the small placement scorer, not the 2^m subset sweep.  (Normal-only
    plans — ``allow_preempt=False`` — take the cheaper `normal_evaluator`
    instead of this program.)  Returns int32[5 + `WIN_FIELDS`]: the
    normal winner (found, node, tier, gpu_mask, cg_mask) followed by the
    preemption winner."""
    pidx, gidx, pbuf, _ = _split_aux(aux, p, g, row_width(victims.shape[2]))
    if p:
        nodestate, victims, drain = _overlay(nodestate, victims, drain,
                                             pidx, pbuf)
    with jax.named_scope("normal_cycle"):
        norm = normal_cycle_core(nodestate, ng, nc, cpb, spec=spec)

    def _skip(_):
        return jnp.zeros(WIN_FIELDS, jnp.int32)

    def _preempt(_):
        return _sorting_winner(nodestate, victims, drain, gidx,
                               thresh, ng, nc, cpb, alpha,
                               spec=spec, m=m, g=g)

    pre = jax.lax.cond(norm[0] > 0, _skip, _preempt, None)
    return jnp.concatenate([norm, pre])


def _req_args(req):
    """Unpack the traced int32[4] request vector (`request_vector`)."""
    return req[0], req[1], req[2], req[3]


@lru_cache(maxsize=None)
def request_vector(thresh: int, ng: int, nc: int, cpb: int) -> jnp.ndarray:
    """One preemptor class as the device int32[4] ``(priority, need_gpus,
    need_cgs, cgs_per_bundle)`` every single-request evaluator takes.

    The request is a TRACED argument, never part of the jit key: one
    compiled program per shape serves every preemptor class (a day of
    mixed classes compiles each bucket once), and it is the same program
    the randomized parity suites run for every class.  Cached per class,
    so a plan uploads nothing for it."""
    return obs.upload(np.array([thresh, ng, nc, cpb], np.int32))


#: in/out layouts (`cluster.mesh_program`) of the evaluators over the
#: resident tensors: node-axis tensors sharded, the plan's packed argument
#: buffer and the request vector replicated, the winner vector replicated
_RESIDENT_IN = "nvnrr"
#: the batch merge/plan evaluators: the six class tensors (the vmapped
#: `batch_class_evaluator`'s result, request axis first), then as above
_BATCH_IN = "vvvvnn" + "nvn" + "rrrr"


@lru_cache(maxsize=None)
def resident_evaluator(mesh, spec: ServerSpec, m: int, p: int, g: int,
                       alpha: float):
    """jit of `_plan_pipeline` (the request is the traced ``req``
    argument, see `request_vector`; alpha is baked in).  The vmapped
    `batch_class_evaluator` takes the request as its vmap axis instead."""

    def f(nodestate, victims, drain, aux, req):
        return _plan_pipeline(nodestate, victims, drain, aux,
                              *_req_args(req), alpha,
                              spec=spec, m=m, p=p, g=g)

    return mesh_program("resident_sweep", f, mesh, _RESIDENT_IN)


@lru_cache(maxsize=None)
def plan_evaluator(mesh, spec: ServerSpec, m: int, p: int, g: int,
                   alpha: float):
    """jit of `_plan2_pipeline` (normal cycle chained into sourcing) — the
    whole ``schedule_or_preempt`` hot path is this one dispatch."""

    def f(nodestate, victims, drain, aux, req):
        return _plan2_pipeline(nodestate, victims, drain, aux,
                               *_req_args(req), alpha,
                               spec=spec, m=m, p=p, g=g)

    return mesh_program("plan2", f, mesh, _RESIDENT_IN)


def _normal_pipeline(nodestate, aux, ng, nc, cpb, *, spec, p):
    """Nodestate-only patch overlay + the normal-cycle scorer (``aux``:
    the packed ``[pidx | pbuf]`` buffer)."""
    if p:
        pidx, _, pbuf, _ = _split_aux(aux, p, 0, aux.shape[0] // p - 1)
        nodestate = _overlay_ns(nodestate, pidx, pbuf)
    with jax.named_scope("normal_cycle"):
        return normal_cycle_core(nodestate, ng, nc, cpb, spec=spec)


@lru_cache(maxsize=None)
def normal_evaluator(mesh, spec: ServerSpec, p: int):
    """jit of `_normal_pipeline` (``req`` as in `resident_evaluator`; its
    priority field is unused).

    The batch sessions use this as their per-plan normal cycle (one small
    [NODE_FIELDS, N] dispatch instead of the host python node loop)."""

    def f(nodestate, aux, req):
        _, ng, nc, cpb = _req_args(req)
        return _normal_pipeline(nodestate, aux, ng, nc, cpb, spec=spec, p=p)

    return mesh_program("normal_cycle", f, mesh, "nrr")


def _gathered_pipeline(nodestate, victims, drain, aux,
                       thresh, ng, nc, cpb, alpha, *, spec, m, p):
    """Patch overlay, then DEVICE-SIDE gather of the rows named by
    ``gidx`` (wide nodes, or a batch plan's delta nodes) and the fused
    pipeline over just those rows.  ``IDX_SENTINEL`` entries gather zero
    rows whose sentinel node id can never win.  ``aux`` is the packed
    ``[pidx | gidx | pbuf]`` buffer; the gather width follows from its
    static length."""
    width = row_width(victims.shape[2])
    g = aux.shape[0] - p * (1 + width)
    pidx, gidx, pbuf, _ = _split_aux(aux, p, g, width)
    if p:
        nodestate, victims, drain = _overlay(nodestate, victims, drain,
                                             pidx, pbuf)
    with jax.named_scope("exact_sweep"):
        ns = jnp.take(nodestate, gidx, axis=1, mode="fill", fill_value=0)
        vv = jnp.take(victims, gidx, axis=1, mode="fill", fill_value=0)
        dd = jnp.take(drain, gidx, axis=1, mode="fill", fill_value=0)
        ns = ns.at[NS_NODE_ID].set(gidx)
        cls = _fused_class_core(ns, vv, dd, thresh, ng, nc, cpb, alpha,
                                spec=spec, m=m, narrow_gate=False)
        win = _fused_argmax_core(ns[NS_NODE_ID], cls, alpha)
    with jax.named_scope("winner_place"):
        return winner_place(win, ns[NS_FREE_GPU], ns[NS_FREE_CG],
                            vv[VF_GPU], vv[VF_CG], ng, nc, cpb, spec=spec)


@lru_cache(maxsize=None)
def gathered_evaluator(mesh, spec: ServerSpec, m: int, p: int, alpha: float):
    """jit of `_gathered_pipeline` (``req`` as in `resident_evaluator`)."""

    def f(nodestate, victims, drain, aux, req):
        return _gathered_pipeline(nodestate, victims, drain, aux,
                                  *_req_args(req), alpha,
                                  spec=spec, m=m, p=p)

    return mesh_program("gathered", f, mesh, _RESIDENT_IN)


# ---------------------------------------------------------------------------------
# Two-stage shortlist sourcing: equivalence-class prescreen + top-K exact sweep
# ---------------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShortlistConfig:
    """Knobs of the two-stage shortlist sourcing front-end.

    ``k`` is the number of representative rows the stage-1 prescreen keeps
    for the exact stage-2 subset sweep.  ``mode``:

    * ``"guaranteed"`` — bit-identical decisions to the full sweep: the
      prescreen bound is admissible, and whenever the in-dispatch
      certainty check cannot PROVE the winner beats every excluded row's
      upper bound, the caller re-dispatches the full sweep.
    * ``"best_effort"`` — fixed-K latency cap: the shortlist winner is
      returned even when uncertain (admission control under a latency
      SLO; the winner is still an exactly-evaluated feasible candidate,
      merely not provably the global argmax).
    """

    k: int = 128
    mode: str = "guaranteed"

    def __post_init__(self) -> None:
        if self.k <= 0:
            raise ValueError(f"shortlist k must be positive, got {self.k}")
        if self.mode not in ("guaranteed", "best_effort"):
            raise ValueError(f"unknown shortlist mode {self.mode!r}")


def _prescreen_core(nodestate, victims, drain, rep,
                    thresh, ng, nc, cpb, alpha, *, spec, k):
    """Stage 1: admissible per-row Eq. 2 upper bound + top-K selection.

    Per row, from the resident aggregates alone (no subset axis): free the
    ENTIRE eligible victim prefix at once and tier the result — any real
    subset frees a sub-mask, and the tier score is monotone in freed
    resources; take ``1/max(min eligible priority, 1)`` as the priority
    term — any non-empty subset's priority sum is at least the minimum.
    Both terms therefore upper-bound every subset's exact score (the empty
    subset is bounded by its own EXACT score from the free masks).  The
    same combination of f32 ops as the argmax keeps the bound monotone
    under rounding.

    Rows gated out (`rep` = False non-representatives, wide/overflow rows
    the host re-dispatches, sentinel padding, bound -inf) never enter the
    shortlist.  Returns ``(gidx int32[k], excl_ub f32[])``: the gather
    indices of the top-K surviving rows (sentinel-padded so short fills
    gather dead rows) and the best bound left OUTSIDE the shortlist — the
    stage-2 certainty reference.
    """
    free_gpu = nodestate[NS_FREE_GPU]
    free_cg = nodestate[NS_FREE_CG]
    node_ids = nodestate[NS_NODE_ID]
    overflow = nodestate[NS_OVERFLOW] != 0
    next_prio = nodestate[NS_NEXT_PRIO]
    vg = victims[VF_GPU]
    vc = victims[VF_CG]
    vp = victims[VF_PRIO]
    stored = victims[VF_STORED] != 0

    consts = spec_constants(spec)
    numa_g = consts["numa_gpu_masks"]
    numa_c = consts["numa_cg_masks"]
    sock_onehot = consts["sock_onehot"]

    elig = stored & (vp < thresh)                            # [N, cap]
    elig_n = jnp.sum(elig.astype(jnp.int32), axis=1)         # [N]
    # victim masks are pairwise disjoint and disjoint from free: freeing
    # the whole eligible prefix is a sum, same trick as the subset fold
    eg = free_gpu + jnp.sum(jnp.where(elig, vg, 0), axis=1)
    ec = free_cg + jnp.sum(jnp.where(elig, vc, 0), axis=1)
    cnt_g = jax.lax.population_count(eg[:, None] & numa_g[None, :])
    cnt_c = jax.lax.population_count(ec[:, None] & numa_c[None, :])
    et = tier_from_counts_dyn(cnt_g, cnt_c, sock_onehot, ng, nc, cpb)
    cnt_fg = jax.lax.population_count(free_gpu[:, None] & numa_g[None, :])
    cnt_fc = jax.lax.population_count(free_cg[:, None] & numa_c[None, :])
    ft = tier_from_counts_dyn(cnt_fg, cnt_fc, sock_onehot, ng, nc, cpb)

    tier_vals = jnp.asarray(tuple(TIER_SCORES) + (0.0,), jnp.float32)
    min_p = jnp.min(jnp.where(elig, vp, _INT32_MAX), axis=1)
    pterm = jnp.where(min_p > 0,
                      1.0 / jnp.maximum(min_p, 1).astype(jnp.float32), 1.0)
    neg = jnp.float32(-jnp.inf)
    # k=0: the empty subset's score is exact (prio term is 1.0 by
    # definition); k>0: tier of the all-eligible-freed masks + min-prio term
    ub0 = jnp.where(ft < 3, alpha * 1.0 + (1.0 - alpha) * tier_vals[ft], neg)
    ubk = jnp.where((elig_n > 0) & (et < 3),
                    alpha * pterm + (1.0 - alpha) * tier_vals[et], neg)
    ub = jnp.maximum(ub0, ubk)

    ok = ((node_ids < _INT32_MAX) & rep & (elig_n <= NARROW_M)
          & ~(overflow & (next_prio < thresh)) & (ub > neg))
    ubm = jnp.where(ok, ub, neg)
    topv, topi = jax.lax.top_k(ubm, k)      # ties break toward lower index
    live = topv > neg
    gidx = jnp.where(live, topi, _INT32_MAX).astype(jnp.int32)
    selm = jnp.zeros(ubm.shape[0], bool).at[topi].set(live)
    excl_ub = jnp.max(jnp.where(ok & ~selm, ub, neg))
    return gidx, excl_ub


def _shortlist_winner(nodestate, victims, drain, rep,
                      thresh, ng, nc, cpb, alpha, *, spec, k):
    """Prescreen → gather K rows → exact sweep → certainty check.

    Stage 2 is the `NARROW_M`-wide exact pipeline over just the gathered
    rows (the prescreen's ``elig <= NARROW_M`` gate makes the width
    sufficient, so the mid tier needs no separate dispatch).  Returns
    int32[`WIN_FIELDS` + 2]: the placed winner vector followed by the
    winner's REAL node id (the argmax row indexes the gathered axis) and
    the certainty flag — 1 iff the winner's exact score STRICTLY beats
    the best admissible bound left outside the shortlist (or, with no
    winner, iff nothing was left outside), which proves the full sweep
    could not have decided differently.
    """
    with jax.named_scope("prescreen"):
        gidx, excl_ub = _prescreen_core(nodestate, victims, drain, rep,
                                        thresh, ng, nc, cpb, alpha,
                                        spec=spec, k=k)
    with jax.named_scope("topk_gather"):
        ns = jnp.take(nodestate, gidx, axis=1, mode="fill", fill_value=0)
        vv = jnp.take(victims, gidx, axis=1, mode="fill", fill_value=0)
        dd = jnp.take(drain, gidx, axis=1, mode="fill", fill_value=0)
        ns = ns.at[NS_NODE_ID].set(gidx)
    with jax.named_scope("exact_sweep"):
        cls = _fused_class_core(ns, vv, dd, thresh, ng, nc, cpb, alpha,
                                spec=spec, m=NARROW_M, narrow_gate=False)
        win = _fused_argmax_core(ns[NS_NODE_ID], cls, alpha)
    with jax.named_scope("winner_place"):
        placed = winner_place(win, ns[NS_FREE_GPU], ns[NS_FREE_CG],
                              vv[VF_GPU], vv[VF_CG], ng, nc, cpb, spec=spec)
    found = win[0] > 0
    tier_vals = jnp.asarray(tuple(TIER_SCORES), jnp.float32)
    pp = win[4]
    prio_term = jnp.where(pp > 0,
                          1.0 / jnp.maximum(pp, 1).astype(jnp.float32), 1.0)
    wscore = alpha * prio_term + (1.0 - alpha) * tier_vals[win[2]]
    certain = jnp.where(found, wscore > excl_ub,
                        excl_ub == jnp.float32(-jnp.inf))
    node_id = jnp.where(found, gidx[win[1]], jnp.int32(-1))
    return jnp.concatenate([placed, jnp.stack([node_id,
                                               certain.astype(jnp.int32)])])


def _shortlist_args(nodestate, victims, drain, aux, *, p, f, rep_sh):
    """Unpack a shortlist plan's ``[pidx | fidx | pbuf | rep words]``
    buffer: overlay the ``p`` patch rows and force the ``f`` rep-mask
    corrections (patched rows carry stale fingerprints: the rows
    themselves plus the promoted lowest unpatched member of each patched
    row's old class) into the unpacked rep mask."""
    pidx, fidx, pbuf, words = _split_aux(aux, p, f,
                                         row_width(victims.shape[2]))
    rep = _rep_mask(words, nodestate.shape[1], rep_sh)
    if p:
        nodestate, victims, drain = _overlay(nodestate, victims, drain,
                                             pidx, pbuf)
    if f:
        rep = rep.at[fidx].set(True, mode="drop")
    return nodestate, victims, drain, rep


def _shortlist_pipeline(nodestate, victims, drain, aux,
                        thresh, ng, nc, cpb, alpha, *, spec, k, p, f,
                        rep_sh=None):
    """`_shortlist_args`, then the two-stage `_shortlist_winner` — one
    dispatch, one small readback."""
    nodestate, victims, drain, rep = _shortlist_args(
        nodestate, victims, drain, aux, p=p, f=f, rep_sh=rep_sh)
    return _shortlist_winner(nodestate, victims, drain, rep,
                             thresh, ng, nc, cpb, alpha, spec=spec, k=k)


def _shortlist_plan2_pipeline(nodestate, victims, drain, aux,
                              thresh, ng, nc, cpb, alpha, *, spec, k, p, f,
                              rep_sh=None):
    """`_plan2_pipeline`'s shortlisted twin: normal cycle first, the
    two-stage preemptive chain only under ``lax.cond`` when it found
    nothing.  Returns int32[5 + `WIN_FIELDS` + 2]."""
    nodestate, victims, drain, rep = _shortlist_args(
        nodestate, victims, drain, aux, p=p, f=f, rep_sh=rep_sh)
    with jax.named_scope("normal_cycle"):
        norm = normal_cycle_core(nodestate, ng, nc, cpb, spec=spec)

    def _skip(_):
        return jnp.zeros(WIN_FIELDS + 2, jnp.int32)

    def _preempt(_):
        return _shortlist_winner(nodestate, victims, drain, rep,
                                 thresh, ng, nc, cpb, alpha,
                                 spec=spec, k=k)

    pre = jax.lax.cond(norm[0] > 0, _skip, _preempt, None)
    return jnp.concatenate([norm, pre])


@lru_cache(maxsize=None)
def _rep_sharding(mesh):
    """1-D row sharding of the equivalence-class rep mask, unpacked on the
    device so the shortlist prescreen stays shard-local up to its top-K
    collective (None on one device)."""
    if mesh is None:
        return None
    return NamedSharding(mesh, P(tuple(mesh.axis_names)))


@lru_cache(maxsize=None)
def shortlist_evaluator(mesh, spec: ServerSpec, k: int, p: int, f: int,
                        alpha: float):
    """jit of `_shortlist_pipeline` (``req`` as in `resident_evaluator`)."""
    rep_sh = _rep_sharding(mesh)

    def fn(nodestate, victims, drain, aux, req):
        return _shortlist_pipeline(nodestate, victims, drain, aux,
                                   *_req_args(req), alpha,
                                   spec=spec, k=k, p=p, f=f, rep_sh=rep_sh)

    return mesh_program("shortlist", fn, mesh, _RESIDENT_IN)


@lru_cache(maxsize=None)
def shortlist_plan_evaluator(mesh, spec: ServerSpec, k: int, p: int, f: int,
                             alpha: float):
    """jit of `_shortlist_plan2_pipeline` — the shortlisted
    ``schedule_or_preempt`` hot path in one dispatch."""
    rep_sh = _rep_sharding(mesh)

    def fn(nodestate, victims, drain, aux, req):
        return _shortlist_plan2_pipeline(nodestate, victims, drain, aux,
                                         *_req_args(req), alpha,
                                         spec=spec, k=k, p=p, f=f,
                                         rep_sh=rep_sh)

    return mesh_program("shortlist_plan", fn, mesh, _RESIDENT_IN)


@lru_cache(maxsize=None)
def batch_class_evaluator(mesh, spec: ServerSpec, m: int, alpha: float):
    """jit(vmap) of the class core over a REQUEST axis: one dispatch
    computes every request's per-node class winners against one snapshot.
    The request scalars are the vmap axis (necessarily dynamic); alpha is
    shared across the batch and baked in."""

    def f(nodestate, victims, drain, thresh, ng, nc, cpb):
        return _fused_class_core(nodestate, victims, drain, thresh, ng, nc,
                                 cpb, alpha, spec=spec, m=m,
                                 narrow_gate=True)

    return mesh_program("batch_class",
                        jax.vmap(f, in_axes=(None, None, None, 0, 0, 0, 0)),
                        mesh, "nvnrrrr", ClassWinners(*"vvvvnn"))


def _masked_class_winner(anyc, cb, pp, um, kn, cnt, nodestate, victims,
                         drain, i, didx, gidx,
                         thresh, ng, nc, cpb, alpha, *, spec, m, g):
    """Masked-class merge shared by the batch evaluators.

    Masks the ``didx`` delta rows out of request ``i``'s precomputed class
    tensors, evaluates the ``g`` gathered rows (dense delta rows plus the
    untouched mid-tier rows the gate excluded) at slot width ``m`` against
    the ALREADY-OVERLAID resident tensors, and reduces everything through
    the Eq. 2 argmax + winner placement.  Class-data rows that can win are
    non-delta rows, where the overlaid arrays equal the raw resident state
    — safe placement inputs."""
    with jax.named_scope("exact_sweep"):
        n = anyc.shape[1]
        mask = jnp.ones(n, bool).at[didx].set(False, mode="drop")
        cls = ClassWinners(anyc[i] & mask[:, None], cb[i], pp[i], um[i],
                           kn[i], cnt[i] * mask)
        node_ids = jnp.arange(n, dtype=jnp.int32)
        fg_cat = nodestate[NS_FREE_GPU]
        fc_cat = nodestate[NS_FREE_CG]
        vg_cat = victims[VF_GPU]
        vc_cat = victims[VF_CG]
        if g:
            ns = jnp.take(nodestate, gidx, axis=1, mode="fill", fill_value=0)
            vv = jnp.take(victims, gidx, axis=1, mode="fill", fill_value=0)
            dd = jnp.take(drain, gidx, axis=1, mode="fill", fill_value=0)
            ns = ns.at[NS_NODE_ID].set(gidx)
            cls_g = _fused_class_core(ns, vv, dd, thresh, ng, nc, cpb,
                                      alpha, spec=spec, m=m,
                                      narrow_gate=False)
            cls = ClassWinners(*(jnp.concatenate([a, b])
                                 for a, b in zip(cls, cls_g)))
            node_ids = jnp.concatenate([node_ids, ns[NS_NODE_ID]])
            fg_cat = jnp.concatenate([fg_cat, ns[NS_FREE_GPU]])
            fc_cat = jnp.concatenate([fc_cat, ns[NS_FREE_CG]])
            vg_cat = jnp.concatenate([vg_cat, vv[VF_GPU]])
            vc_cat = jnp.concatenate([vc_cat, vv[VF_CG]])
        win = _fused_argmax_core(node_ids, cls, alpha)
    with jax.named_scope("winner_place"):
        return winner_place(win, fg_cat, fc_cat, vg_cat, vc_cat,
                            ng, nc, cpb, spec=spec)


def _batch_merge_pipeline(anyc, cb, pp, um, kn, cnt, nodestate, victims,
                          drain, i, aux, pbuf, thresh, ng, nc, cpb, alpha,
                          *, spec, m, dpad, g):
    """Patch overlay + `_masked_class_winner` (the batch merge body).

    ``aux`` layout: ``[:dpad]`` mask rows, then the patch rows (``pbuf``
    row order matches), then the gather rows."""
    p = pbuf.shape[0]
    if p:
        nodestate, victims, drain = _overlay(nodestate, victims, drain,
                                             aux[dpad:dpad + p], pbuf)
    return _masked_class_winner(
        anyc, cb, pp, um, kn, cnt, nodestate, victims, drain, i,
        aux[:dpad], aux[dpad + p:], thresh, ng, nc, cpb, alpha,
        spec=spec, m=m, g=g)


@lru_cache(maxsize=None)
def batch_merge_evaluator(mesh, spec: ServerSpec, m: int, dpad: int, g: int,
                          alpha: float):
    """Per-request device merge for the batch session, ONE dispatch.

    jit of `_batch_merge_pipeline`: a batched plan whose deltas are all
    narrow costs exactly one dispatch and one int32[`WIN_FIELDS`]
    readback, like a single-request plan."""

    def f(anyc, cb, pp, um, kn, cnt, nodestate, victims, drain, i, aux,
          pbuf, req):
        return _batch_merge_pipeline(
            anyc, cb, pp, um, kn, cnt, nodestate, victims, drain, i, aux,
            pbuf, *_req_args(req), alpha, spec=spec, m=m, dpad=dpad, g=g)

    return mesh_program("batch_merge", f, mesh, _BATCH_IN)


def _batch_plan_pipeline(anyc, cb, pp, um, kn, cnt, nodestate, victims,
                         drain, i, aux, pbuf, thresh, ng, nc, cpb, alpha,
                         *, spec, m, dpad, g, p):
    """`_batch_merge_pipeline` with the NORMAL CYCLE chained in front.

    The ``p`` patch rows cover EVERY delta row of the view (wide and
    overflow rows included) so the normal-cycle scorer sees the plan's
    exact free masks; the masked-class preemptive merge runs under
    ``lax.cond`` only when the normal cycle places nothing.  Returns
    int32[5 + `WIN_FIELDS`]."""
    if p:
        nodestate, victims, drain = _overlay(nodestate, victims, drain,
                                             aux[dpad:dpad + p], pbuf)
    with jax.named_scope("normal_cycle"):
        norm = normal_cycle_core(nodestate, ng, nc, cpb, spec=spec)

    def _skip(_):
        return jnp.zeros(WIN_FIELDS, jnp.int32)

    def _pre(_):
        return _masked_class_winner(
            anyc, cb, pp, um, kn, cnt, nodestate, victims, drain, i,
            aux[:dpad], aux[dpad + p:], thresh, ng, nc, cpb, alpha,
            spec=spec, m=m, g=g)

    return jnp.concatenate([norm, jax.lax.cond(norm[0] > 0, _skip,
                                               _pre, None)])


@lru_cache(maxsize=None)
def batch_plan_evaluator(mesh, spec: ServerSpec, m: int, dpad: int, g: int,
                         p: int, alpha: float):
    """jit of `_batch_plan_pipeline` — a batched plan is one dispatch end
    to end, same as a single-request plan."""

    def f(anyc, cb, pp, um, kn, cnt, nodestate, victims, drain, i, aux,
          pbuf, req):
        return _batch_plan_pipeline(
            anyc, cb, pp, um, kn, cnt, nodestate, victims, drain, i, aux,
            pbuf, *_req_args(req), alpha, spec=spec, m=m, dpad=dpad, g=g,
            p=p)

    return mesh_program("batch_plan", f, mesh, _BATCH_IN)


#: node-axis chunk size for the widest (m=16) victim bucket: keeps the
#: [chunk, 2^16, U] popcount intermediates to tens of MB per dispatch.
MAX_ROWS_WIDE = 16


class CandidateShortlist(list):
    """``list[Candidate]`` that also reports the TRUE candidate count.

    The fused engine returns only per-dispatch winners, but the device
    already counted every feasible min-k subset; ``n_candidates`` carries
    that count so ``SchedulingDecision.num_candidates`` stays comparable
    with the exhaustive-listing engines.

    ``placements`` maps ``(node, victims)`` of device-decoded winners to
    the concrete `Placement` the dispatch's §3.4 scorer committed — the
    scheduler binds those masks directly instead of re-running the host
    ``place()`` on the winning node (python-fallback candidates have no
    entry and keep the host path).
    """

    n_candidates: int = 0

    def __init__(self, *args) -> None:
        super().__init__(*args)
        self.placements: dict[tuple[int, tuple[int, ...]], Placement] = {}


def _req_scalars(spec: ServerSpec, workload: WorkloadSpec):
    """(need_gpus, need_cgs, cgs_per_bundle) for the dynamic-request cores."""
    ng = workload.gpus_per_instance
    nc = workload.coregroups_per_instance(spec.coregroup_size)
    bundle = workload.numa_policy == TopoPolicy.GUARANTEED
    return ng, nc, (nc // ng if (bundle and ng) else 0)


@lru_cache(maxsize=None)
def _empty_patch_args(cap: int):
    """Cached zero-size device ``(aux, pbuf)`` arrays of a batch session's
    delta-free plan — the common case allocates nothing per plan."""
    pa = _pack_patches({}, cap)
    return obs.upload(pa.idx), obs.upload(pa.host)


@lru_cache(maxsize=None)
def _empty_aux():
    """The cached zero-length argument buffer of a plan with nothing to
    send (no patch rows, no gather rows): such a plan uploads nothing."""
    return obs.upload(np.zeros(0, np.int32))


def _patch_row(patches, node: int) -> VictimRow:
    """Exact host row for one delta node (`ViewDelta` encodes lazily)."""
    if isinstance(patches, ViewDelta):
        return patches.row(node)
    return patches[node]


def _patch_elig(patches, thresh: int):
    """``(eligible-count, truncation-risk)`` dicts over the delta nodes.

    `ViewDelta` computes its dense rows vectorized from the descriptor
    metadata (no per-node host encode); plain `VictimRow` dicts are read
    row by row."""
    if isinstance(patches, ViewDelta):
        return patches.elig_bad(thresh)
    return ({n: int(((r.vp < thresh) & r.stored).sum())
             for n, r in patches.items()},
            {n: bool(r.overflow and r.next_priority < thresh)
             for n, r in patches.items()})


class PatchArgs(NamedTuple):
    """A plan's overlay rows, before they travel (`_plan_aux`).

    ``p`` is the overlay bucket (0: none); ``idx`` the ``int32[p]`` row
    indices, device-encoded rows first; ``host`` the host-packed flattened
    rows that follow them; ``dev`` the device-encoded rows (a `ViewDelta`'s
    dense rows, ``int32[d, width]`` on the device) or None."""

    p: int
    idx: np.ndarray
    host: np.ndarray
    dev: object = None


#: the empty index vector of a plan without gather or forced rows
_NO_IDX = np.zeros(0, np.int32)


@lru_cache(maxsize=None)
def _merge_dev_rows(prefix: int):
    """The packed buffer with device-encoded patch rows spliced in after
    its ``prefix`` index words, ahead of the host-packed rows."""
    return obs.program("patch_concat", lambda aux, dbuf: jnp.concatenate(
        [aux[:prefix], dbuf.reshape(-1), aux[prefix:]]))


def _plan_aux(pa: PatchArgs, sel=_NO_IDX, words=None):
    """ONE host→device transfer of a plan's arguments.

    The host writes them into one contiguous int32 buffer in the order the
    evaluators slice it at static offsets (`_split_aux`): the patch
    indices, the gather or forced-rep indices ``sel``, the flattened patch
    rows, then the shortlist's bit-packed rep mask ``words``.  Upload cost
    is per call, not per byte, so one buffer replaces one upload per
    array.  Device-encoded rows (``pa.dev``) are spliced in on the device.
    A plan with nothing to send gets the cached empty buffer."""
    parts = [pa.idx, sel, pa.host.reshape(-1)]
    if words is not None:
        parts.append(words)
    host = np.concatenate(parts).astype(np.int32, copy=False)
    if pa.dev is not None:
        return _merge_dev_rows(len(pa.idx) + len(sel))(obs.upload(host),
                                                       pa.dev)
    return obs.upload(host) if len(host) else _empty_aux()


def _memo_args(dcs: DeviceClusterState, key, patches, p: int, build):
    """``build()`` — a plan's routing and its uploaded argument buffer —
    kept in ``dcs.plan_cache`` under ``key`` while the state ``version``
    and the overlay bucket ``p`` hold, so a plan against unchanged state
    uploads nothing.  At one version the pending rows change only when a
    flush empties them (``p`` moves to 0).  Plans with view-delta rows are
    not kept: their rows are the view's, not the state's."""
    if patches:
        return build()
    hit = dcs.plan_cache.get(key)
    if hit is not None and hit[0] == (dcs.version, p):
        return hit[1]
    got = build()
    dcs.plan_cache[key] = ((dcs.version, p), got)
    return got


def _patch_args(dcs: DeviceClusterState, patches) -> PatchArgs:
    """One overlay covering the view's delta rows (``patches``) AND the
    device state's unflushed ``pending`` rows (``sync(flush=False)``):
    both classes of stale row ride the same in-dispatch scatter, so the
    plan hot path pays zero standalone scatter dispatches.

    ``patches`` is a ``{node: VictimRow}`` dict (legacy / batch-session
    paths) or a `ViewDelta`: its dense rows are then rebuilt ON DEVICE by
    the delta encoder and only fallback rows are host-packed."""
    cap = dcs.cap
    width = row_width(cap)
    pending = sorted(set(dcs.pending) - set(patches))
    dense = None
    bufs, ids = [], []
    if isinstance(patches, ViewDelta):
        dense = patches.device_rows(dcs)
        if patches.fallback:
            nodes = sorted(patches.fallback)
            bufs.append(flatten_rows(*pack_rows(
                [patches.fallback[n] for n in nodes], nodes, cap)))
            ids.extend(nodes)
    elif patches:
        nodes = sorted(patches)
        bufs.append(flatten_rows(
            *pack_rows([patches[n] for n in nodes], nodes, cap)))
        ids.extend(nodes)
    if pending:
        bufs.append(flatten_rows(*pack_context_rows(dcs.mirror, pending)))
        ids.extend(pending)
    hidx, hbuf = _NO_IDX, np.zeros((0, width), np.int32)
    if ids:
        hidx = _pad_idx(ids)
        hbuf = np.concatenate(bufs) if len(bufs) > 1 else bufs[0]
        if len(hidx) > len(ids):
            hbuf = np.pad(hbuf, ((0, len(hidx) - len(ids)), (0, 0)))
    if dense is None:
        return PatchArgs(len(hidx), hidx, hbuf)
    # disjoint node sets by construction; sentinel pads drop out of the
    # overlay scatter, so per-section pow2 buckets concatenate directly
    didx, dbuf = dense
    idx = np.concatenate([didx, hidx])
    return PatchArgs(len(idx), idx, hbuf, dbuf)


def _pad_idx(ids, floor: int = 4) -> np.ndarray:
    """`cluster.pad_idx` with the dispatch paths' minimum bucket of 4."""
    return pad_idx(ids, floor)


def _pack_patches(patches: dict[int, VictimRow], cap: int) -> PatchArgs:
    """Pack view-delta rows for the in-dispatch overlay: host rows
    flattened (see `flatten_rows`) and padded to a power-of-two bucket
    (sentinel indices are dropped by the scatter); ``p`` = 0 when there
    are no patches, selecting the overlay-free jit variant."""
    if not patches:
        return PatchArgs(0, _NO_IDX, np.zeros((0, row_width(cap)), np.int32))
    nodes = sorted(patches)
    buf = flatten_rows(*pack_rows([patches[n] for n in nodes], nodes, cap))
    idx = _pad_idx(nodes)
    if len(idx) > len(nodes):
        buf = np.pad(buf, ((0, len(idx) - len(nodes)), (0, 0)))
    return PatchArgs(len(idx), idx, buf)


class FusedSplit(NamedTuple):
    """Host routing decision for one fused sourcing call.

    ``m_res`` is the victim-slot width of the MAIN dispatch (adaptive: 4
    when only a handful of rows hold more than 4 eligible victims — a
    16-combo program is ~4x cheaper than the 256-combo one); ``mid`` holds
    the rows with m_res < eligible <= `NARROW_M` (gathered m=8 chunks),
    ``wide`` the 9..16-eligible rows (gathered 2^16-subset chunks) and
    ``overflow`` the truncated rows whose eligible victims extend past the
    stored prefix (per-node python fallback)."""

    m_res: int
    mid: list
    wide: list
    overflow: list


#: smallest victim-slot width of the adaptive resident program
MIN_M = 4


def split_fused_nodes(dcs: DeviceClusterState, patches: dict, thresh: int,
                      nodes=None, gate: int | None = None):
    """Route rows between the main dispatch and its re-dispatch tiers.

    Eligible victims are a prefix of each (priority, uid)-sorted row, so
    every row is classified by one vectorized count over the host mirror
    (patched rows overridden).  ``gate`` pins the main-dispatch width
    (the shortlist and the batch session run at a fixed width); when None,
    the width adapts: `MIN_M` if at most `MAX_ROWS_WIDE` rows exceed it.

    The [n, cap] scan is skipped when no row can route out: when no node
    stores more victims (``dcs.count_max``), and no patch row holds more,
    than the width ``w`` (``gate`` capped at `NARROW_M`, or `MIN_M` when
    adaptive).  Then no row has ``elig > w`` (mid, wide) and none is
    truncated (``count > cap >= w``), so the split is exactly empty.
    """
    ctx = dcs.mirror
    m = MIN_M if gate is None else gate
    w = min(m, NARROW_M)
    if isinstance(patches, ViewDelta):
        patch_big = any(patches.count(n) > w for n in patches)
    else:
        patch_big = any(r.count > w for r in patches.values())
    if dcs.count_max <= w and not patch_big:
        return FusedSplit(m, [], [], [])
    obs.count("route_scans")
    n = dcs.cluster.num_nodes
    elig = ((ctx.vp < thresh) & ctx.stored).sum(axis=1)
    bad = ctx.overflow & (ctx.next_prio < thresh)
    p_elig, p_bad = _patch_elig(patches, thresh)
    for node, e in p_elig.items():
        elig[node] = e
    for node, b in p_bad.items():
        bad[node] = b
    if nodes is None:
        allowed = np.ones(n, bool)
    else:
        allowed = np.zeros(n, bool)
        allowed[list(nodes)] = True
    ok = allowed & ~bad
    m_res = gate
    if m_res is None:
        m_res = MIN_M if int((ok & (elig > MIN_M)).sum()) <= MAX_ROWS_WIDE \
            else NARROW_M
    mid = np.nonzero(ok & (elig > m_res) & (elig <= NARROW_M))[0].tolist()
    wide = np.nonzero(ok & (elig > NARROW_M))[0].tolist()
    overflow = np.nonzero(allowed & bad)[0].tolist()
    return FusedSplit(m_res, mid, wide, overflow)


def _append_winner(out: CandidateShortlist, res, sel_nodes, patches, ctx):
    """Decode one dispatch's int32[`WIN_FIELDS`] winner into a host
    `Candidate` plus its device-committed `Placement`.

    Dispatches run asynchronously; callers queue (res, sel_nodes) pairs and
    decode them together at the end so one device sync covers all of them.
    """
    found, row, tier, combo, prio, _k, ncand, pgm, pcm = obs.readback(res)
    out.n_candidates += ncand
    if not found:
        return
    if sel_nodes is None:
        node = row                        # node axis == resident row index
    elif isinstance(sel_nodes, dict):
        node = int(sel_nodes.get(row, row))   # combined resident+mid rows
    else:
        node = int(sel_nodes[row])        # gathered chunk
    vu = _patch_row(patches, node).vu if node in patches else ctx.vu[node]
    uids = [int(vu[j]) for j in range(len(vu)) if (combo >> j) & 1]
    victims = tuple(sorted(uids))
    out.append(Candidate(node=node, victims=victims, tier=tier,
                         priority_sum=prio))
    out.placements[(node, victims)] = Placement(
        gpu_mask=pgm & 0xFFFFFFFF, cg_mask=pcm & 0xFFFFFFFF, tier=tier)


def _fast_plan_args(dcs: DeviceClusterState, patches, thresh: int,
                    pa: PatchArgs):
    """Routing split + the packed ``[pidx | gidx | pbuf]`` argument buffer
    of a nodes=None dispatch, in one upload.  Returns ``(split, g, aux)``.

    Without view-delta rows it is kept per preemptor priority on the
    `DeviceClusterState` (`_memo_args`): repeated plans against unchanged
    state skip the host eligibility scan AND the upload — the whole host
    side of a plan is then one dict lookup."""

    def build():
        split = split_fused_nodes(dcs, patches, thresh)
        gidx = _pad_idx(split.mid) if split.mid else _NO_IDX
        return split, len(gidx), _plan_aux(pa, gidx)

    with obs.span("sched.route"):
        return _memo_args(dcs, thresh, patches, pa.p, build)


def _forced_rows(dcs: DeviceClusterState, patches) -> list[int]:
    """Rep-mask corrections for view-delta patch rows.

    The rep mask is computed from the MIRROR's fingerprints, but
    patch rows are overlaid with different content in-dispatch, so (a)
    every patched row must be treated as its own (possibly new) class —
    forced into the rep set — and (b) a patched row may have been the
    representative of its old class, orphaning the unpatched members:
    promote the lowest unpatched member of each patched row's old class.
    Extra representatives only add rows to the prescreen (harmless for
    exactness); only a MISSING representative could hide the argmax, and
    these two corrections close exactly the ways one can go missing.
    Pending rows (``sync(flush=False)`` leftovers) need nothing: their
    mirror fingerprints are fresh, so the rep assignment already matches
    the content the overlay installs.  Class members come from the
    mirror's class index (`SourcingContext.classes`), in ascending order.
    """
    pset = {int(n) for n in patches} if patches else set()
    if not pset:
        return []
    ctx = dcs.mirror
    members = ctx.classes().members
    forced = set(pset)
    for d in pset:
        for mbr in members[int(ctx.fp[d])]:
            if mbr not in pset:
                forced.add(mbr)
                break
    return sorted(forced)


def _shortlist_plan_args(dcs: DeviceClusterState, patches, thresh: int,
                         pa: PatchArgs):
    """`_fast_plan_args`'s shortlist twin: wide/overflow routing split +
    forced-rep indices + the rep mask (`DeviceClusterState.rep_classes`),
    bit-packed, all in the plan's one ``[pidx | fidx | pbuf | rep words]``
    upload, kept per preemptor priority while the state holds
    (`_memo_args`).  After commits the host work follows the rows that
    changed: the class index files only those, and the routing scan runs
    only when some row stores more than `NARROW_M` victims.  Returns
    ``(split, f, aux)``."""

    def build():
        # gate=NARROW_M: the shortlist's stage 2 always runs NARROW_M
        # wide, so only genuinely wide (elig > NARROW_M) and overflow rows
        # route out
        split = split_fused_nodes(dcs, patches, thresh, gate=NARROW_M)
        forced = _forced_rows(dcs, patches)
        fidx = _pad_idx(forced) if forced else _NO_IDX
        return split, len(fidx), _plan_aux(pa, fidx,
                                           pack_bits(dcs.rep_classes()))

    with obs.span("sched.route"):
        return _memo_args(dcs, ("shortlist", thresh), patches, pa.p, build)


def source_candidates_fused(
    cluster, workload: WorkloadSpec, nodes: list[int] | None = None,
    alpha: float = DEFAULT_ALPHA, shortlist: ShortlistConfig | None = None,
) -> list[Candidate]:
    """Fused cluster-wide IMP over the device-resident state.

    ``nodes=None`` (the scheduler's ``fused_filter`` path) runs Guaranteed
    Filtering + sourcing + Eq. 2 selection over ALL nodes in one dispatch
    against `DeviceClusterState` — zero per-node host work; view deltas ride
    along as in-dispatch patch rows.  An explicit node list (legacy callers,
    per-node ``source``) gathers exactly those rows device-side instead.

    Returns the winning `Candidate` per dispatch (plus per-node python
    candidates for overflow rows) as a `CandidateShortlist` carrying the
    true evaluated-candidate count; the scheduler's ``select`` reduces the
    shortlist with the exact host-side Eq. 2.  Winner parity with ``imp``
    is covered by tests/test_fused_sourcing.py.
    """
    if nodes is not None and not nodes:
        return CandidateShortlist()
    spec = cluster.spec
    base = getattr(cluster, "base", cluster)
    # flush=False: small dirty sets stay pending and ride the dispatch's
    # patch overlay instead of paying a standalone scatter dispatch
    dcs = base.device_state().sync(flush=False)
    ctx = dcs.mirror
    thresh = workload.priority
    ng, nc, cpb = _req_scalars(spec, workload)
    with obs.span("sched.patch"):
        if nodes is None:
            patches = _view_patches_of(cluster, dcs)
        else:
            delta = (set(cluster.delta_nodes())
                     if hasattr(cluster, "delta_nodes") else set())
            delta &= set(nodes)
            patches = {d: encode_row(cluster, d, ctx.cap)
                       for d in sorted(delta)}
        pa = _patch_args(dcs, patches)
    p = pa.p
    alpha = float(alpha)
    rq = request_vector(thresh, ng, nc, cpb)
    pending = []     # dispatches are async: launch all, decode once
    if nodes is None:
        sl_vals = None
        if shortlist is not None and dcs.n_rows > shortlist.k:
            # stage 1+2 shortlist dispatch; the decoded certainty flag
            # decides whether the full sweep is still required
            split, f, aux_d = _shortlist_plan_args(dcs, patches, thresh,
                                                   pa)
            dcs.counts["shortlist_plans"] += 1
            res = shortlist_evaluator(dcs.mesh, spec, shortlist.k, p, f,
                                      alpha)(
                dcs.nodestate, dcs.victims, dcs.drain, aux_d, rq)
            vals = obs.readback(res)
            if vals[-1] or shortlist.mode != "guaranteed":
                sl_vals = vals
            else:
                dcs.fallbacks["shortlist_full_sweep"] += 1
        if sl_vals is not None:
            mid = []     # absorbed: stage 2 always runs NARROW_M wide
            out = CandidateShortlist(_overflow_candidates(
                cluster, workload, split.overflow, dcs.fallbacks))
            out.n_candidates = len(out)
            pending.append((np.asarray(sl_vals[:WIN_FIELDS], np.int32),
                            {sl_vals[1]: sl_vals[WIN_FIELDS]}))
        else:
            # the whole pipeline — overlay, Filtering, m_res-wide subsets
            # over ALL rows, the gathered mid tier, and the Eq. 2 argmax —
            # is ONE dispatch; its arguments travel as one upload (cached
            # with the routing split while the state version holds)
            split, g, aux_d = _fast_plan_args(dcs, patches, thresh, pa)
            mid = split.mid
            out = CandidateShortlist(_overflow_candidates(
                cluster, workload, split.overflow, dcs.fallbacks))
            out.n_candidates = len(out)
            res = resident_evaluator(dcs.mesh, spec, split.m_res, p, g, alpha)(
                dcs.nodestate, dcs.victims, dcs.drain, aux_d, rq)
            n = dcs.n_rows
            sel = ({n + j: node for j, node in enumerate(mid)}
                   if mid else None)
            pending.append((res, sel))
            mid = []     # consumed by the combined dispatch
    else:
        split = split_fused_nodes(dcs, patches, thresh, nodes)
        mid = split.mid
        out = CandidateShortlist(_overflow_candidates(
            cluster, workload, split.overflow, dcs.fallbacks))
        out.n_candidates = len(out)
        excluded = set(mid) | set(split.wide) | set(split.overflow)
        narrow = [c for c in nodes if c not in excluded]
        if narrow:
            res = gathered_evaluator(dcs.mesh, spec, split.m_res, p, alpha)(
                dcs.nodestate, dcs.victims, dcs.drain,
                _plan_aux(pa, _pad_idx(narrow)), rq)
            pending.append((res, narrow))
    for m, rows in ((NARROW_M, mid), (ctx.cap, split.wide)):
        for lo in range(0, len(rows), MAX_ROWS_WIDE):
            chunk = rows[lo:lo + MAX_ROWS_WIDE]
            res = gathered_evaluator(dcs.mesh, spec, m, p, alpha)(
                dcs.nodestate, dcs.victims, dcs.drain,
                _plan_aux(pa, _pad_idx(chunk)), rq)
            pending.append((res, chunk))
    for res, sel in pending:
        _append_winner(out, res, sel, patches, ctx)
    return out


# ---------------------------------------------------------------------------------
# End-to-end device-resident Algorithm 1 (normal cycle chained into sourcing)
# ---------------------------------------------------------------------------------

@dataclasses.dataclass
class FusedPlanResult:
    """Decoded outcome of one chained normal+preemptive dispatch.

    ``placement`` carries the dispatch's §3.4 device-scorer masks; a
    ``None`` placement on a preempted result (python-fallback winner)
    tells the scheduler to place on the host instead."""

    kind: str                               # placed | preempted | rejected
    node: int = -1
    placement: Placement | None = None
    victims: tuple[int, ...] = ()
    n_candidates: int = 0


def _view_patches_of(cluster, dcs: DeviceClusterState):
    """Delta-row descriptors for a ClusterView ({} for the base cluster).

    The fused ``nodes=None`` paths get a `ViewDelta`: dense rows are
    rebuilt by the IN-DISPATCH delta encoder straight from the planned
    bind/evict/restore masks the view carries, so the per-plan host work
    is O(delta instances) descriptor math — no ``encode_row`` victim sort
    per dirty row, and patch rows never round-trip through python."""
    if not hasattr(cluster, "delta_nodes"):
        return {}
    return ViewDelta(cluster, dcs.mirror, dcs.pending)


def plan_normal_fused(cluster, workload: WorkloadSpec):
    """The normal scheduling cycle as ONE small device dispatch.

    `placement_jax.normal_cycle_core` over the resident nodestate (view
    deltas and unflushed dirty rows overlaid in-dispatch): the host's
    ``_plan_normal`` python node loop and per-node ``place()`` collapse to
    a [NODE_FIELDS, N] program returning the winner's node and concrete
    masks.  Returns ``(node, Placement)`` or ``None`` — the batch sessions'
    per-plan normal cycle.
    """
    spec = cluster.spec
    base = getattr(cluster, "base", cluster)
    dcs = base.device_state().sync(flush=False)
    with obs.span("sched.patch"):
        patches = _view_patches_of(cluster, dcs)
        pa = _patch_args(dcs, patches)
        aux_d = _memo_args(dcs, "normal", patches, pa.p,
                           lambda: _plan_aux(pa))
    ng, nc, cpb = _req_scalars(spec, workload)
    res = normal_evaluator(dcs.mesh, spec, pa.p)(
        dcs.nodestate, aux_d,
        request_vector(workload.priority, ng, nc, cpb))
    found, node, tier, gm, cm = obs.readback(res)
    if not found:
        return None
    return node, Placement(gpu_mask=gm & 0xFFFFFFFF,
                           cg_mask=cm & 0xFFFFFFFF, tier=tier)


def _finalize_plan(vals, sel, patches, ctx, shortlist_fn, wide_chunks_fn,
                   alpha: float) -> FusedPlanResult:
    """Shared decode of a chained dispatch's int32[5 + WIN_FIELDS] readback.

    ``shortlist_fn`` builds the base `CandidateShortlist` (python-fallback
    overflow candidates) and ``wide_chunks_fn`` yields the chunked wide-row
    re-dispatches as ``(res, chunk)`` pairs — both LAZY, consumed only when
    the normal cycle placed nothing, so a placed plan never pays for them.
    """
    with obs.span("sched.decode"):
        nfound, nnode, ntier, ngm, ncm = vals[:5]
        if nfound:
            return FusedPlanResult("placed", nnode, Placement(
                gpu_mask=ngm & 0xFFFFFFFF, cg_mask=ncm & 0xFFFFFFFF,
                tier=ntier))
        out = shortlist_fn()
        _append_winner(out, np.asarray(vals[5:], np.int32), sel, patches,
                       ctx)
        for res, chunk in wide_chunks_fn():
            _append_winner(out, res, chunk, patches, ctx)
        if not out:
            return FusedPlanResult("rejected",
                                   n_candidates=out.n_candidates)
        chosen = select_best(out, alpha)
        return FusedPlanResult(
            "preempted", chosen.node,
            out.placements.get((chosen.node, chosen.victims)),
            chosen.victims, out.n_candidates)


def _plan_fused_shortlist(cluster, workload: WorkloadSpec,
                          dcs: DeviceClusterState, ctx, patches,
                          pa: PatchArgs, alpha: float,
                          shortlist: ShortlistConfig):
    """The shortlisted chained plan: one `_shortlist_plan2_pipeline`
    dispatch + decode.  Returns None when the certainty check failed in
    guaranteed mode — the caller then re-dispatches the full sweep (the
    resident tensors and patch buffers are already in place, so the
    fallback costs one extra dispatch, no host rework)."""
    spec = cluster.spec
    thresh = workload.priority
    ng, nc, cpb = _req_scalars(spec, workload)
    alpha = float(alpha)
    rq = request_vector(thresh, ng, nc, cpb)
    split, f, aux_d = _shortlist_plan_args(dcs, patches, thresh, pa)
    dcs.counts["shortlist_plans"] += 1
    res = shortlist_plan_evaluator(dcs.mesh, spec, shortlist.k, pa.p, f,
                                   alpha)(
        dcs.nodestate, dcs.victims, dcs.drain, aux_d, rq)
    vals = obs.readback(res)
    if (not vals[0] and not vals[-1]
            and shortlist.mode == "guaranteed"):
        return None
    # the argmax row indexes the gathered K axis; the readback carries the
    # real node id alongside
    sel = {vals[6]: vals[5 + WIN_FIELDS]}

    def shortlist_out():
        out = CandidateShortlist(_overflow_candidates(
            cluster, workload, split.overflow, dcs.fallbacks))
        out.n_candidates = len(out)
        return out

    def wide_chunks():
        for lo in range(0, len(split.wide), MAX_ROWS_WIDE):
            chunk = split.wide[lo:lo + MAX_ROWS_WIDE]
            yield gathered_evaluator(dcs.mesh, spec, ctx.cap, pa.p, alpha)(
                dcs.nodestate, dcs.victims, dcs.drain,
                _plan_aux(pa, _pad_idx(chunk)), rq), chunk

    return _finalize_plan(vals[:5 + WIN_FIELDS], sel, patches, ctx,
                          shortlist_out, wide_chunks, alpha)


def plan_fused(cluster, workload: WorkloadSpec, alpha: float = DEFAULT_ALPHA,
               allow_preempt: bool = True,
               shortlist: ShortlistConfig | None = None) -> FusedPlanResult:
    """BOTH cycles of Algorithm 1 as one device dispatch (engine hook for
    ``fused_place`` scheduling).

    The chained program (`plan_evaluator`) overlays view deltas, runs the
    normal-cycle argmin + placement scorer over ALL nodes and — only when
    that finds nothing, via ``lax.cond`` — Guaranteed Filtering, the
    subset sweep, the Eq. 2 argmax, and the winner's placement.  One
    ``int32[5 + WIN_FIELDS]`` readback decides the whole plan; rare wide
    (9..16-eligible) rows re-dispatch chunked afterwards and truncated
    overflow rows fall back to per-node python, exactly like
    `source_candidates_fused`.

    With a `ShortlistConfig` (and more rows than ``k``) the preemptive
    chain runs the two-stage shortlist program instead: equivalence-class
    + top-K prescreen, exact sweep over K gathered rows.  In guaranteed
    mode a failed certainty check falls back to the full sweep below, so
    decisions stay bit-identical to ``shortlist=None``.
    """
    if not allow_preempt:
        got = plan_normal_fused(cluster, workload)
        if got is None:
            return FusedPlanResult("rejected")
        return FusedPlanResult("placed", got[0], got[1])
    spec = cluster.spec
    base = getattr(cluster, "base", cluster)
    dcs = base.device_state().sync(flush=False)
    ctx = dcs.mirror
    thresh = workload.priority
    ng, nc, cpb = _req_scalars(spec, workload)
    with obs.span("sched.patch"):
        patches = _view_patches_of(cluster, dcs)
        pa = _patch_args(dcs, patches)
    if shortlist is not None and dcs.n_rows > shortlist.k:
        got = _plan_fused_shortlist(cluster, workload, dcs, ctx,
                                    patches, pa, alpha, shortlist)
        if got is not None:
            return got
        # guaranteed-mode certainty check failed: full sweep decides
        dcs.fallbacks["shortlist_full_sweep"] += 1
    split, g, aux_d = _fast_plan_args(dcs, patches, thresh, pa)
    mid = split.mid
    alpha = float(alpha)
    rq = request_vector(thresh, ng, nc, cpb)
    res = plan_evaluator(dcs.mesh, spec, split.m_res, pa.p, g, alpha)(
        dcs.nodestate, dcs.victims, dcs.drain, aux_d, rq)
    vals = obs.readback(res)
    n = dcs.n_rows
    sel = {n + j: node for j, node in enumerate(mid)} if mid else None

    def shortlist():
        out = CandidateShortlist(_overflow_candidates(
            cluster, workload, split.overflow, dcs.fallbacks))
        out.n_candidates = len(out)
        return out

    def wide_chunks():
        # wide rows re-dispatch only now that the normal cycle is known
        # to have failed — they are unreachable work otherwise
        for lo in range(0, len(split.wide), MAX_ROWS_WIDE):
            chunk = split.wide[lo:lo + MAX_ROWS_WIDE]
            yield gathered_evaluator(dcs.mesh, spec, ctx.cap, pa.p, alpha)(
                dcs.nodestate, dcs.victims, dcs.drain,
                _plan_aux(pa, _pad_idx(chunk)), rq), chunk

    return _finalize_plan(vals, sel, patches, ctx, shortlist, wide_chunks,
                          alpha)


class BatchSourcingSession:
    """`plan_batch` sourcing: ALL requests vmapped in one dispatch.

    At construction, ONE jit dispatch evaluates every request's per-node
    class winners against the shared snapshot (`batch_class_evaluator`:
    the request axis is a vmap axis of dynamic (priority, need) scalars) —
    the tensors stay on device.  ``source(view, workload, i)`` then
    preserves the sequential planned-eviction semantics exactly: request
    *i*'s winner is the device merge of (a) the precomputed class data with
    the view's delta rows masked out and (b) a small gathered re-dispatch
    of just those delta rows patched to the view state.  Untouched rows are
    never re-evaluated or re-uploaded.

    Sessions PERSIST across ``plan_batch`` calls (`persistent_batch_session`):
    the snapshot tensors and precomputed class data stay valid until a
    cluster mutation arrives through ``invalidate_node``, so bursty
    admission of the same request classes pays the big vmapped dispatch
    once per burst, not once per call.  ``reset_view_caches()`` drops the
    per-view row-encode cache on reuse (a fresh view restarts its
    node-version counters).
    """

    def __init__(self, cluster: Cluster, workloads, alpha: float) -> None:
        self.cluster = cluster
        self.spec = cluster.spec
        self.alpha = float(alpha)
        self.dcs = cluster.device_state().sync()
        self.ctx = self.dcs.mirror
        self._row_cache: dict[int, tuple[int, VictimRow]] = {}
        self.reqs = [(wl.priority,) + _req_scalars(self.spec, wl)
                     for wl in workloads]
        #: reuse key of `persistent_batch_session` (alpha + request scalars)
        self.cache_key = (self.alpha, tuple(self.reqs))
        # adaptive gate, like the single-request path: precompute the class
        # data at MIN_M when every request leaves at most MAX_ROWS_WIDE
        # rows above it (those ride each merge dispatch's gather section).
        # The snapshot is fixed, so all per-thresh scans dedupe.
        self.gate = MIN_M
        self._split_cache: dict[int, FusedSplit] = {}
        if self.dcs.count_max > MIN_M:
            for t in {t for t, _, _, _ in self.reqs}:
                elig = ((self.ctx.vp < t) & self.ctx.stored).sum(axis=1)
                if int((elig > MIN_M).sum()) > MAX_ROWS_WIDE:
                    self.gate = NARROW_M
                    break
        rp = _pad_pow2(len(self.reqs))
        th = np.zeros(rp, np.int32)           # pad: nothing eligible ...
        ng = np.full(rp, _INT32_MAX, np.int32)   # ... and nothing feasible
        nc = np.full(rp, _INT32_MAX, np.int32)
        cpb = np.zeros(rp, np.int32)
        for j, (t, g, c, b) in enumerate(self.reqs):
            th[j], ng[j], nc[j], cpb[j] = t, g, c, b
        self.class_data = batch_class_evaluator(
            self.dcs.mesh, self.spec, self.gate, self.alpha)(
            self.dcs.nodestate, self.dcs.victims, self.dcs.drain,
            obs.upload(th), obs.upload(ng), obs.upload(nc),
            obs.upload(cpb))

    def reset_view_caches(self) -> None:
        """Drop per-view state before serving a new ``plan_batch`` call
        (row encodings are keyed by `ClusterView.node_version`, which a
        fresh view restarts at zero)."""
        self._row_cache.clear()

    def _view_patches(self, view, delta) -> dict:
        """Encode the view's delta rows, re-encoding ONLY rows a later plan
        touched since they were last cached (`ClusterView.node_version`)."""
        patches = {}
        for d in delta:
            ver = view.node_version(d)
            hit = self._row_cache.get(d)
            if hit is None or hit[0] != ver:
                hit = (ver, encode_row(view, d, self.ctx.cap))
                self._row_cache[d] = hit
            patches[d] = hit[1]
        return patches

    def _route(self, view, thresh: int):
        """Delta routing shared by ``source`` and ``plan``.

        Encodes the view's delta rows and classifies every row against the
        session split for this preemptor priority (cached: the snapshot is
        fixed): untouched mid/wide/overflow rows minus the deltas, plus the
        delta rows partitioned into overflow (python fallback), wide
        (chunked 2^cap re-dispatch) and dense (merged-dispatch gather)."""
        delta = sorted(view.delta_nodes())
        patches = self._view_patches(view, delta)
        dset = set(delta)
        split = self._split_cache.get(thresh)
        if split is None:
            split = split_fused_nodes(self.dcs, {}, thresh, gate=self.gate)
            self._split_cache[thresh] = split
        mid = [w for w in split.mid if w not in dset]
        wide = [w for w in split.wide if w not in dset]
        overflow = [o for o in split.overflow if o not in dset]
        over = {d for d in delta if patches[d].overflow
                and patches[d].next_priority < thresh}
        elig = {d: int(((patches[d].vp < thresh) & patches[d].stored).sum())
                for d in delta if d not in over}
        return (delta, patches, mid, wide, overflow, sorted(over),
                [d for d in elig if elig[d] > NARROW_M],
                [d for d in elig if elig[d] <= NARROW_M])

    def source(self, view, workload: WorkloadSpec,
               i: int) -> CandidateShortlist:
        thresh, ng, nc, cpb = self.reqs[i]
        ctx = self.ctx
        cap = ctx.cap
        n = self.dcs.n_rows
        # class data was precomputed at ``self.gate``: rows above the gate
        # (minus this plan's delta rows) ride the merge dispatch's gather
        # section (mid) or the chunked 2^cap re-dispatch (wide)
        (delta, patches, mid, wide, overflow, d_over, d_wide,
         d_dense) = self._route(view, thresh)
        out = CandidateShortlist(_overflow_candidates(
            view, workload, overflow, self.dcs.fallbacks))
        out.n_candidates = len(out)
        rq = request_vector(thresh, ng, nc, cpb)
        pending = []     # dispatches are async: launch all, decode once
        if d_over:       # delta rows that cannot ride the merged dispatch
            extra = _overflow_candidates(view, workload, d_over,
                                         self.dcs.fallbacks)
            out.extend(extra)
            out.n_candidates += len(extra)
        # ONE dispatch: request i's class tensors minus its delta rows,
        # merged with a NARROW_M-wide pass over the patched dense delta
        # rows AND the untouched mid-tier rows the gate excluded
        pa = _pack_patches({d: patches[d] for d in d_dense}, cap)
        gather = sorted(d_dense) + mid
        didx = _pad_idx(delta) if delta else _NO_IDX
        gidx = _pad_idx(gather) if gather else _NO_IDX
        if len(didx) == 0 and len(gidx) == 0:
            aux_d, pbuf_d = _empty_patch_args(cap)
        else:
            aux_d = obs.upload(np.concatenate([didx, pa.idx, gidx]))
            pbuf_d = obs.upload(pa.host)
        res = batch_merge_evaluator(self.dcs.mesh, self.spec, NARROW_M,
                                    len(didx), len(gidx), self.alpha)(
            *self.class_data, self.dcs.nodestate, self.dcs.victims,
            self.dcs.drain, obs.upload(np.asarray(i, np.int32)), aux_d,
            pbuf_d, rq)
        sel = {n + j: node for j, node in enumerate(gather)}
        pending.append((res, sel))
        # wide rows (9..16 eligible victims): chunked 2^cap dispatches —
        # patched delta rows and untouched rows alike
        if d_wide or wide:
            pw = _pack_patches({d: patches[d] for d in d_wide}, cap)
            rows = d_wide + wide
            for lo in range(0, len(rows), MAX_ROWS_WIDE):
                chunk = rows[lo:lo + MAX_ROWS_WIDE]
                res = gathered_evaluator(self.dcs.mesh, self.spec, cap,
                                         pw.p, self.alpha)(
                    self.dcs.nodestate, self.dcs.victims, self.dcs.drain,
                    _plan_aux(pw, _pad_idx(chunk)), rq)
                pending.append((res, chunk))
        for res, sel in pending:
            _append_winner(out, res, sel, patches, ctx)
        return out

    def plan(self, view, workload: WorkloadSpec,
             i: int) -> FusedPlanResult:
        """Both Algorithm 1 cycles for batched request ``i``, ONE dispatch.

        `batch_plan_evaluator`: the normal-cycle scorer runs over the
        view-overlaid resident nodestate (EVERY delta row patched, so the
        plan sees its exact free masks), and the masked-class preemptive
        merge runs under ``lax.cond`` only when it places nothing —
        placement masks decoded either way, sequential planned-eviction
        semantics preserved exactly as in ``source``."""
        thresh, ng, nc, cpb = self.reqs[i]
        ctx = self.ctx
        cap = ctx.cap
        n = self.dcs.n_rows
        with obs.span("sched.patch"):
            (delta, patches, mid, wide, overflow, d_over, d_wide,
             d_dense) = self._route(view, thresh)
            # ALL delta rows ride the overlay (wide/overflow included): the
            # normal cycle needs the view's exact free masks everywhere
            pa = _pack_patches(patches, cap)
            gather = sorted(d_dense) + mid
            didx = _pad_idx(delta) if delta else _NO_IDX
            gidx = _pad_idx(gather) if gather else _NO_IDX
            if len(didx) == 0 and len(gidx) == 0 and pa.p == 0:
                aux_d, pbuf_d = _empty_patch_args(cap)
            else:
                aux_d = obs.upload(np.concatenate([didx, pa.idx, gidx]))
                pbuf_d = obs.upload(pa.host)
        rq = request_vector(thresh, ng, nc, cpb)
        res = batch_plan_evaluator(self.dcs.mesh, self.spec, NARROW_M,
                                   len(didx), len(gidx), pa.p, self.alpha)(
            *self.class_data, self.dcs.nodestate, self.dcs.victims,
            self.dcs.drain, obs.upload(np.asarray(i, np.int32)), aux_d,
            pbuf_d, rq)
        vals = obs.readback(res)
        sel = {n + j: node for j, node in enumerate(gather)}

        def shortlist():
            out = CandidateShortlist(_overflow_candidates(
                view, workload, overflow, self.dcs.fallbacks))
            out.n_candidates = len(out)
            if d_over:
                extra = _overflow_candidates(view, workload, d_over,
                                             self.dcs.fallbacks)
                out.extend(extra)
                out.n_candidates += len(extra)
            return out

        def wide_chunks():
            # wide rows re-dispatch only now that the normal cycle is
            # known to have failed
            if not d_wide and not wide:
                return
            pw = _pack_patches({d: patches[d] for d in d_wide}, cap)
            rows = d_wide + wide
            for lo in range(0, len(rows), MAX_ROWS_WIDE):
                chunk = rows[lo:lo + MAX_ROWS_WIDE]
                yield gathered_evaluator(self.dcs.mesh, self.spec, cap,
                                         pw.p, self.alpha)(
                    self.dcs.nodestate, self.dcs.victims, self.dcs.drain,
                    _plan_aux(pw, _pad_idx(chunk)), rq), chunk

        return _finalize_plan(vals, sel, patches, ctx, shortlist,
                              wide_chunks, self.alpha)


def persistent_batch_session(cluster: Cluster, workloads,
                             alpha: float) -> BatchSourcingSession:
    """``batch_factory`` hook with cross-call session reuse.

    The first call on a cluster registers one ``invalidate_node`` listener
    that voids the cached session on ANY mutation (bind/evict/restore) —
    the same choke point that keeps the resident device state coherent.
    A ``plan_batch`` burst with unchanged cluster state and the same
    request classes (and alpha) then reuses the session: the precomputed
    vmapped class tensors are served again and only the per-plan merge
    dispatches run.  Any mismatch or staleness rebuilds transparently.

    The slot lives ON the cluster object (like ``device_state()``), so a
    dropped cluster and its cached session are reference-cycle garbage
    the collector reclaims together — no global registry pins them.
    """
    entry = getattr(cluster, "_batch_session_slot", None)
    if entry is None:
        entry = {"session": None}

        def _void(node, _entry=entry):
            _entry["session"] = None

        cluster.add_dirty_listener(_void)
        cluster._batch_session_slot = entry
    spec = cluster.spec
    key = (float(alpha),
           tuple((wl.priority,) + _req_scalars(spec, wl) for wl in workloads))
    session = entry["session"]
    if session is not None and session.cache_key == key:
        session.reset_view_caches()
        session.dcs.counts["batch_reuses"] += 1
        obs.count("batch_reuses")
        return session
    with obs.span("sched.batch_session"):
        session = BatchSourcingSession(cluster, workloads, alpha)
    session.dcs.counts["batch_builds"] += 1
    obs.count("batch_builds")
    entry["session"] = session
    return session


def warmup_fused(cluster: Cluster, alpha: float = DEFAULT_ALPHA,
                 batch: int = 8, workloads=None,
                 shortlist: ShortlistConfig | None = None) -> None:
    """Pre-compile the fused jit buckets for this cluster's shapes.

    Opt-in via ``TopoScheduler(..., warmup=True)``: drives REAL sourcing
    sweeps (pure reads against copy-on-write views, so cluster state is
    untouched) for each preemptor class — once against the clean state and
    once against a view with a delta — plus one `plan_batch` session, so
    the jit variants a first plan actually hits (resident evaluator with
    and without a patch bucket, the request-vmapped class evaluator, the
    per-request merge) are compiled at construction instead of on the
    first plans (cold P90 is compile-dominated otherwise).

    ``workloads`` defaults to the Table 3 classes; pass the deployment's
    own preemptor classes when they differ (the request is traced, but
    each class's priority picks its own slot-width and gather buckets).
    """
    from .workload import table3_workloads

    if workloads is None:
        workloads = table3_workloads()
    workloads = list(workloads)
    cluster.device_state().sync()
    for wl in workloads:
        source_candidates_fused(cluster, wl, None, alpha=alpha)
        plan_fused(cluster, wl, alpha=alpha)       # chained Algorithm 1
        plan_normal_fused(cluster, wl)             # batch-path normal cycle
        if shortlist is not None:
            plan_fused(cluster, wl, alpha=alpha, shortlist=shortlist)
        view = cluster.view()
        for node in range(cluster.num_nodes):    # fabricate one view delta
            victims = view.victims_on(node, wl.priority)
            if victims:
                view.plan_evict(victims[0].uid)
                source_candidates_fused(view, wl, None, alpha=alpha)
                plan_fused(view, wl, alpha=alpha)
                plan_normal_fused(view, wl)
                if shortlist is not None:
                    plan_fused(view, wl, alpha=alpha, shortlist=shortlist)
                break
    if batch > 1 and workloads:
        session = BatchSourcingSession(
            cluster, tuple((workloads * batch)[:batch]), alpha)
        session.source(cluster.view(), workloads[0], 0)
        session.plan(cluster.view(), workloads[0], 0)


register_engine("imp_batched", batched=True, needs_alpha=True,
                fused_filter=True, fused_place=True, plan_fn=plan_fused,
                normal_fn=plan_normal_fused,
                batch_factory=persistent_batch_session,
                warmup_fn=warmup_fused,
                supports_shortlist=True)(source_candidates_fused)

# full-sweep parity oracle: identical functions, shortlist disabled — the
# scheduler's shortlist kwargs are ignored, every plan runs the all-rows
# subset sweep (tests/benchmarks compare decisions against this engine)
register_engine("imp_batched_full", batched=True, needs_alpha=True,
                fused_filter=True, fused_place=True, plan_fn=plan_fused,
                normal_fn=plan_normal_fused,
                batch_factory=persistent_batch_session,
                warmup_fn=warmup_fused)(source_candidates_fused)
