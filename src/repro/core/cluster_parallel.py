"""Cluster-scale candidate sourcing sharded over the device mesh.

Beyond the paper: at 10^4–10^5 nodes, even vectorized subset evaluation on one
host dominates scheduling latency.  Here the *node* axis of the batched
evaluator is sharded across all mesh devices (every device scores its local
slice of servers), and the Eq. 1/Eq. 2 argmax reduces globally — XLA lowers
the reduction to all-reduce collectives across pods.  ``lower_distributed_source``
is compiled by the multi-pod dry-run to prove the scheduler itself scales to
the production mesh.

The **``imp_sharded`` engine** goes beyond dry-run lowering: it installs a
`ShardedDeviceClusterState` (the resident nodestate/victims/drain tensors
`NamedSharding`-split on the node axis over a 1-D mesh of every local
device) and routes the full fused dispatch chain — `preemption_jax`'s
`plan_fused` / `plan_normal_fused` / `source_candidates_fused` / batch
sessions, UNCHANGED — through `sharded_evaluators`: jits of the very same
pipeline bodies with explicit sharding constraints.  Per-node filtering,
subset sweeps and class reductions stay shard-local; only the final Eq. 2
argmax chain (and the one-row winner gather) crosses shards.  Decisions are
bit-identical to ``imp_batched`` (see tests/test_distributed.py).
"""
from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import obs
from .cluster import (DRAIN_FIELDS, IDX_SENTINEL, NODE_FIELDS, NS_FREE_CG,
                      NS_FREE_GPU, NS_NODE_ID, VF_CG, VF_GPU, VICTIM_FIELDS,
                      DeviceClusterState, apply_packed_rows,
                      encode_delta_core, pack_scatter)
from .engines import register_engine
from .placement_jax import normal_cycle_core, winner_place
from .preemption_jax import (Request, _evaluate_subsets_core,
                             _fused_argmax_core, _fused_class_core,
                             _sorting_winner, combo_table, spec_constants)
from . import preemption_jax as _pj
from .scoring import DEFAULT_ALPHA, TIER_SCORES
from .topology import ServerSpec

_TIER_VALUES = tuple(TIER_SCORES) + (0.0,)


def _source_best(
    free_gpu, free_cg, vg, vc, vp, valid,
    table, numa_gpu_masks, numa_cg_masks, sock_onehot,
    *, request: Request, alpha: float,
):
    """Evaluate all (node × subset) candidates and reduce to the global best.

    Returns (best_score f32[], best_node i32[], best_combo i32[]) — the
    argmax of Eq. 2 over every candidate in the cluster at this subset size.
    """
    eval_fn = partial(_evaluate_subsets_core, request=request)
    tier, prio, _ = jax.vmap(
        eval_fn, in_axes=(0, 0, 0, 0, 0, 0, None, None, None, None)
    )(free_gpu, free_cg, vg, vc, vp, valid,
      table, numa_gpu_masks, numa_cg_masks, sock_onehot)
    # Eq. 1: S = alpha / sum_priority + (1 - alpha) * T(tier)
    tier_vals = jnp.asarray(_TIER_VALUES, jnp.float32)
    topo = tier_vals[tier]
    prio_term = jnp.where(prio > 0, 1.0 / jnp.maximum(prio, 1).astype(jnp.float32),
                          1.0)
    s = alpha * prio_term + (1.0 - alpha) * topo
    s = jnp.where(tier < 3, s, -jnp.inf)
    flat = s.reshape(-1)
    best = jnp.argmax(flat)                      # global argmax => all-reduce
    n_comb = s.shape[1]
    return flat[best], (best // n_comb).astype(jnp.int32), (
        best % n_comb).astype(jnp.int32)


def make_distributed_source(
    mesh: jax.sharding.Mesh,
    spec: ServerSpec,
    request: Request,
    alpha: float = 0.5,
):
    """jit the cluster-wide sourcing with the node axis sharded over ALL mesh
    axes (data, model, and pod when present)."""
    axes = tuple(mesh.axis_names)
    node_sharding = NamedSharding(mesh, P(axes))        # shard node axis
    repl = NamedSharding(mesh, P())
    fn = partial(_source_best, request=request, alpha=alpha)
    return jax.jit(
        fn,
        in_shardings=(node_sharding,) * 2 + (node_sharding,) * 4 + (repl,) * 4,
        out_shardings=(repl, repl, repl),
    )


def distributed_source_inputs(
    spec: ServerSpec,
    num_nodes: int,
    max_victims: int,
    k: int,
    request: Request,
    rng: np.random.Generator | None = None,
):
    """Build (or synthesize) the dense inputs for the distributed sourcing."""
    rng = rng or np.random.default_rng(0)
    consts = spec_constants(spec)
    table = combo_table(max_victims, k)
    free_gpu = np.zeros(num_nodes, np.int32)
    free_cg = np.zeros(num_nodes, np.int32)
    vg = rng.integers(0, spec.all_gpu_mask + 1, (num_nodes, max_victims),
                      dtype=np.int32)
    vc = rng.integers(0, spec.all_cg_mask + 1, (num_nodes, max_victims),
                      dtype=np.int32)
    vp = rng.integers(100, 600, (num_nodes, max_victims), dtype=np.int32)
    valid = np.ones((num_nodes, max_victims), bool)
    return (free_gpu, free_cg, vg, vc, vp, valid, np.asarray(table),
            np.asarray(consts["numa_gpu_masks"]),
            np.asarray(consts["numa_cg_masks"]),
            np.asarray(consts["sock_onehot"]))


def lower_distributed_source(
    mesh: jax.sharding.Mesh,
    spec: ServerSpec,
    num_nodes: int = 65536,
    max_victims: int = 8,
    k: int = 2,
    alpha: float = 0.5,
):
    """Lower (without executing) the sharded sourcing for the dry-run."""
    request = Request(need_gpus=4, need_cgs=4, bundle_locality=True)
    fn = make_distributed_source(mesh, spec, request, alpha)
    args = distributed_source_inputs(spec, num_nodes, max_victims, k, request)
    shapes = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args]
    return fn.lower(*shapes)


# ---------------------------------------------------------------------------------
# Fused single-dispatch sourcing, sharded
# ---------------------------------------------------------------------------------

def make_distributed_fused_source(
    mesh: jax.sharding.Mesh,
    spec: ServerSpec,
    request: Request,
    alpha: float = 0.5,
    m: int = 8,
):
    """jit the fused Filtering+Sorting evaluator (``imp_batched`` semantics:
    drain-mask Guaranteed Filtering, per-node smallest-k, global Eq. 2
    argmax in one program) over the DEVICE-RESIDENT layout
    (`DeviceClusterState`: nodestate/victims/drain tensors) with the node
    axis sharded over every mesh axis.

    The per-node filtering popcounts, subset evaluation and class
    reductions stay local to each device's node shard; only the final
    argmax chain over the ``[N, 3]`` class winners crosses shards (XLA
    all-reduce collectives) plus the one-row gather that feeds the winner
    through the SAME §3.4 placement scorer the local fused path uses
    (`placement_jax.winner_place`) — the device→host traffic is the
    ``int32[WIN_FIELDS]`` winner vector, concrete GPU/CoreGroup masks
    included, regardless of cluster size.
    """
    axes = tuple(mesh.axis_names)
    node_sharding = NamedSharding(mesh, P(None, axes))   # shard node axis 1
    victim_sharding = NamedSharding(mesh, P(None, axes, None))
    repl = NamedSharding(mesh, P())

    def fn(nodestate, victims, drain, thresh):
        # the SAME body the local fused engine dispatches (g=0: no
        # gathered mid-tier section) — `sharded_evaluators` jits the full
        # overlay/plan variants of it for the `imp_sharded` engine
        return _sorting_winner(
            nodestate, victims, drain, jnp.zeros(0, jnp.int32), thresh,
            jnp.int32(request.need_gpus), jnp.int32(request.need_cgs),
            jnp.int32(request.cgs_per_bundle), jnp.float32(alpha),
            spec=spec, m=m, g=0)

    return obs.program(
        "distributed_fused_source", fn,
        in_shardings=(node_sharding, victim_sharding, node_sharding, repl),
        out_shardings=repl,
    )


def make_distributed_normal_cycle(
    mesh: jax.sharding.Mesh,
    spec: ServerSpec,
    request: Request,
):
    """jit the NORMAL scheduling cycle (`placement_jax.normal_cycle_core`)
    with the node axis sharded over every mesh axis.

    Per-node count screens, placement tiers and the blind degraded
    fallback stay shard-local; the ``(tier, leftover, node)`` argmin chain
    and the winner-row gather feeding the placement scorer reduce across
    shards — the same scorer the single-host fused dispatch chains in
    front of sourcing, so the no-preemption admission path scales to the
    dry-run mesh too.
    """
    axes = tuple(mesh.axis_names)
    node_sharding = NamedSharding(mesh, P(None, axes))
    repl = NamedSharding(mesh, P())

    def fn(nodestate):
        return normal_cycle_core(
            nodestate, jnp.int32(request.need_gpus),
            jnp.int32(request.need_cgs),
            jnp.int32(request.cgs_per_bundle), spec=spec)

    return obs.program("distributed_normal_cycle", fn,
                       in_shardings=(node_sharding,), out_shardings=repl)


def lower_distributed_normal_cycle(
    mesh: jax.sharding.Mesh,
    spec: ServerSpec,
    num_nodes: int = 65536,
):
    """Lower (without executing) the sharded normal cycle for the dry-run."""
    request = Request(need_gpus=4, need_cgs=4, bundle_locality=True)
    fn = make_distributed_normal_cycle(mesh, spec, request)
    shape = jax.ShapeDtypeStruct((NODE_FIELDS, num_nodes), np.int32)
    return fn.lower(shape)


def distributed_fused_inputs(
    spec: ServerSpec,
    num_nodes: int,
    m: int,
    rng: np.random.Generator | None = None,
):
    """Synthesize device-resident-layout inputs for the sharded sourcing.

    One GPU/CoreGroup per victim slot keeps the disjoint-mask invariant the
    fused fold relies on (real inputs are `DeviceClusterState` tensors).
    """
    rng = rng or np.random.default_rng(0)
    nodestate = np.zeros((NODE_FIELDS, num_nodes), np.int32)
    nodestate[NS_NODE_ID] = np.arange(num_nodes, dtype=np.int32)
    victims = np.zeros((VICTIM_FIELDS, num_nodes, m), np.int32)
    victims[0] = 1 << (np.arange(m, dtype=np.int32) % spec.num_gpus)
    victims[1] = 1 << (np.arange(m, dtype=np.int32) % spec.num_coregroups)
    victims[2] = rng.integers(100, 600, (num_nodes, m), dtype=np.int32)
    victims[3] = np.arange(m, dtype=np.int32)
    victims[4] = 1
    drain = np.zeros((DRAIN_FIELDS, num_nodes), np.int32)
    drain[0] = nodestate[0] | np.bitwise_or.reduce(victims[0], axis=1)
    drain[1] = nodestate[1] | np.bitwise_or.reduce(victims[1], axis=1)
    thresh = np.int32(1000)
    return (nodestate, victims, drain, thresh)


def lower_distributed_fused_source(
    mesh: jax.sharding.Mesh,
    spec: ServerSpec,
    num_nodes: int = 65536,
    m: int = 8,
    alpha: float = 0.5,
):
    """Lower (without executing) the sharded fused sourcing for the dry-run."""
    request = Request(need_gpus=4, need_cgs=4, bundle_locality=True)
    fn = make_distributed_fused_source(mesh, spec, request, alpha, m)
    args = distributed_fused_inputs(spec, num_nodes, m)
    shapes = [jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype)
              for a in args]
    return fn.lower(*shapes)


# ---------------------------------------------------------------------------------
# Mesh-sharded resident cluster state (the `imp_sharded` engine)
# ---------------------------------------------------------------------------------

@lru_cache(maxsize=None)
def _cluster_mesh(devices) -> jax.sharding.Mesh:
    return jax.sharding.Mesh(np.asarray(devices), ("nodes",))


def cluster_mesh(devices=None) -> jax.sharding.Mesh:
    """1-D mesh over every local device (axis ``nodes``) — the default
    layout of `ShardedDeviceClusterState`.  Degrades to a one-device mesh
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


@lru_cache(maxsize=None)
def _sharded_scatter(mesh):
    """jit of the dirty-row scatter with the resident tensors held sharded
    on both sides (the packed update rows replicate — they are O(dirty),
    tiny)."""
    node_sh, victim_sh, repl = _mesh_shardings(mesh)
    return obs.program(
        "apply_rows_sharded", apply_packed_rows,
        in_shardings=(node_sh, victim_sh, node_sh, repl),
        out_shardings=(node_sh, victim_sh, node_sh))


@lru_cache(maxsize=None)
def _sharded_delta_encoder(mesh, cap: int, a: int):
    """jit of `cluster.encode_delta_core` against the SHARDED base tensors:
    the per-plan descriptor columns arrive replicated and the rebuilt patch
    rows come back replicated (they are O(delta), tiny), so the base-row
    gather is the only cross-shard traffic."""
    node_sh, victim_sh, repl = _mesh_shardings(mesh)
    return obs.program("delta_encode_sharded",
                       partial(encode_delta_core, cap=cap, a=a),
                       in_shardings=(node_sh, victim_sh) + (repl,) * 10,
                       out_shardings=repl)


class ShardedDeviceClusterState(DeviceClusterState):
    """`DeviceClusterState` with the node axis `NamedSharding`-split over a
    device mesh (install via ``cluster.device_state(sharded=True)``).

    The node axis is padded UP to a multiple of the device count; pad rows
    carry `IDX_SENTINEL` node ids and zero masks, which every fused core
    already excludes (``node_ids < 2**31-1`` screens), so evaluator
    results are bit-identical to the unsharded layout.  ``n_rows`` exposes
    the padded length — the fused paths use it as the row base of their
    gathered sections.  The full-rebuild upload, the dirty-row scatter and
    the view-delta encoder all pin their outputs sharded/replicated
    explicitly so the resident tensors never silently migrate."""

    def __init__(self, cluster, cap: int | None = None, mesh=None) -> None:
        self.mesh = cluster_mesh() if mesh is None else mesh
        self._node_sh, self._victim_sh, self._repl = _mesh_shardings(
            self.mesh)
        super().__init__(cluster, cap)

    @property
    def n_rows(self) -> int:
        d = int(self.mesh.size)
        return -(-max(self.cluster.num_nodes, 1) // d) * d

    def _upload_full(self, ns, v, dr):
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
        return (obs.upload(np.ascontiguousarray(ns), self._node_sh),
                obs.upload(np.ascontiguousarray(v), self._victim_sh),
                obs.upload(np.ascontiguousarray(dr), self._node_sh))

    def _scatter(self, idx, buf):
        return _sharded_scatter(self.mesh)(
            self.nodestate, self.victims, self.drain,
            obs.upload(pack_scatter(idx, buf)))

    def delta_encode(self, a: int, didx, *descs):
        return _sharded_delta_encoder(self.mesh, self.cap, a)(
            self.nodestate, self.victims, didx, *descs)


# ---------------------------------------------------------------------------------
# Sharded twins of the fused evaluator factories
# ---------------------------------------------------------------------------------

class _ShardedEvaluators:
    """Drop-in namespace for `preemption_jax._evals`: the SAME pipeline
    bodies (`_plan_pipeline`, `_plan2_pipeline`, `_normal_pipeline`,
    `_gathered_pipeline`, the batch pipelines) jitted with explicit
    sharding constraints.  Node-axis tensors arrive sharded, each plan's
    packed argument buffer and the request vector replicate, and the
    int32[`WIN_FIELDS`]-sized winner vectors come back replicated — the
    per-node class math runs shard-local and only the final argmax chain
    (plus the winner-row gather) crosses shards as collectives."""

    def __init__(self, mesh) -> None:
        self.mesh = mesh
        self.node_sh, self.victim_sh, self.repl = _mesh_shardings(mesh)
        self._cache: dict = {}

    def _get(self, key, build):
        fn = self._cache.get(key)
        if fn is None:
            fn = self._cache[key] = build()
        return fn

    def resident_evaluator(self, spec, m, p, g, alpha):
        def build():
            def f(nodestate, victims, drain, aux, req):
                return _pj._plan_pipeline(
                    nodestate, victims, drain, aux,
                    *_pj._req_args(req), alpha, spec=spec, m=m, p=p, g=g)

            return obs.program("resident_sweep_sharded", f, in_shardings=(
                self.node_sh, self.victim_sh, self.node_sh, self.repl,
                self.repl), out_shardings=self.repl)

        return self._get(("res", spec, m, p, g, alpha), build)

    def plan_evaluator(self, spec, m, p, g, alpha):
        def build():
            def f(nodestate, victims, drain, aux, req):
                return _pj._plan2_pipeline(
                    nodestate, victims, drain, aux,
                    *_pj._req_args(req), alpha, spec=spec, m=m, p=p, g=g)

            return obs.program("plan2_sharded", f, in_shardings=(
                self.node_sh, self.victim_sh, self.node_sh, self.repl,
                self.repl), out_shardings=self.repl)

        return self._get(("plan", spec, m, p, g, alpha), build)

    def normal_evaluator(self, spec, p):
        def build():
            def f(nodestate, aux, req):
                _, ng, nc, cpb = _pj._req_args(req)
                return _pj._normal_pipeline(nodestate, aux, ng, nc, cpb,
                                            spec=spec, p=p)

            return obs.program("normal_cycle_sharded", f, in_shardings=(
                self.node_sh, self.repl, self.repl),
                out_shardings=self.repl)

        return self._get(("norm", spec, p), build)

    def gathered_evaluator(self, spec, m, p, alpha):
        def build():
            def f(nodestate, victims, drain, aux, req):
                return _pj._gathered_pipeline(
                    nodestate, victims, drain, aux,
                    *_pj._req_args(req), alpha, spec=spec, m=m, p=p)

            return obs.program("gathered_sharded", f, in_shardings=(
                self.node_sh, self.victim_sh, self.node_sh, self.repl,
                self.repl), out_shardings=self.repl)

        return self._get(("gath", spec, m, p, alpha), build)

    @property
    def _rep_sh(self):
        """1-D row sharding of the equivalence-class rep mask, unpacked on
        the device so the shortlist prescreen stays shard-local up to its
        top-K collective."""
        return NamedSharding(self.mesh, P(tuple(self.mesh.axis_names)))

    def shortlist_evaluator(self, spec, k, p, f, alpha):
        def build():
            def fn(nodestate, victims, drain, aux, req):
                return _pj._shortlist_pipeline(
                    nodestate, victims, drain, aux,
                    *_pj._req_args(req), alpha, spec=spec, k=k, p=p, f=f,
                    rep_sh=self._rep_sh)

            return obs.program("shortlist_sharded", fn, in_shardings=(
                self.node_sh, self.victim_sh, self.node_sh, self.repl,
                self.repl), out_shardings=self.repl)

        return self._get(("sl", spec, k, p, f, alpha), build)

    def shortlist_plan_evaluator(self, spec, k, p, f, alpha):
        def build():
            def fn(nodestate, victims, drain, aux, req):
                return _pj._shortlist_plan2_pipeline(
                    nodestate, victims, drain, aux,
                    *_pj._req_args(req), alpha, spec=spec, k=k, p=p, f=f,
                    rep_sh=self._rep_sh)

            return obs.program("shortlist_plan_sharded", fn, in_shardings=(
                self.node_sh, self.victim_sh, self.node_sh, self.repl,
                self.repl), out_shardings=self.repl)

        return self._get(("slplan", spec, k, p, f, alpha), build)

    def batch_class_evaluator(self, spec, m, alpha):
        def build():
            def f(nodestate, victims, drain, thresh, ng, nc, cpb):
                return _fused_class_core(
                    nodestate, victims, drain, thresh, ng, nc, cpb, alpha,
                    spec=spec, m=m, narrow_gate=True)

            cw3, cw2 = self.victim_sh, self.node_sh
            return obs.program("batch_class_sharded", 
                jax.vmap(f, in_axes=(None, None, None, 0, 0, 0, 0)),
                in_shardings=(self.node_sh, self.victim_sh, self.node_sh)
                + (self.repl,) * 4,
                out_shardings=_pj.ClassWinners(cw3, cw3, cw3, cw3, cw2,
                                               cw2))

        return self._get(("bcls", spec, m, alpha), build)

    def batch_merge_evaluator(self, spec, m, dpad, g, alpha):
        def build():
            def f(anyc, cb, pp, um, kn, cnt, nodestate, victims, drain, i,
                  aux, pbuf, req):
                return _pj._batch_merge_pipeline(
                    anyc, cb, pp, um, kn, cnt, nodestate, victims, drain,
                    i, aux, pbuf, *_pj._req_args(req), alpha, spec=spec,
                    m=m, dpad=dpad, g=g)

            cw3, cw2 = self.victim_sh, self.node_sh
            return obs.program("batch_merge_sharded", f, in_shardings=(
                cw3, cw3, cw3, cw3, cw2, cw2, self.node_sh, self.victim_sh,
                self.node_sh, self.repl, self.repl, self.repl, self.repl),
                out_shardings=self.repl)

        return self._get(("bmerge", spec, m, dpad, g, alpha), build)

    def batch_plan_evaluator(self, spec, m, dpad, g, p, alpha):
        def build():
            def f(anyc, cb, pp, um, kn, cnt, nodestate, victims, drain, i,
                  aux, pbuf, req):
                return _pj._batch_plan_pipeline(
                    anyc, cb, pp, um, kn, cnt, nodestate, victims, drain,
                    i, aux, pbuf, *_pj._req_args(req), alpha, spec=spec,
                    m=m, dpad=dpad, g=g, p=p)

            cw3, cw2 = self.victim_sh, self.node_sh
            return obs.program("batch_plan_sharded", f, in_shardings=(
                cw3, cw3, cw3, cw3, cw2, cw2, self.node_sh, self.victim_sh,
                self.node_sh, self.repl, self.repl, self.repl, self.repl),
                out_shardings=self.repl)

        return self._get(("bplan", spec, m, dpad, g, p, alpha), build)


@lru_cache(maxsize=None)
def sharded_evaluators(mesh) -> _ShardedEvaluators:
    """The per-mesh sharded evaluator namespace (`preemption_jax._evals`
    routes here whenever the device state carries a mesh)."""
    return _ShardedEvaluators(mesh)


# ---------------------------------------------------------------------------------
# The `imp_sharded` engine: fused paths over the sharded resident state
# ---------------------------------------------------------------------------------

def _sharded_state(cluster) -> None:
    """Idempotently install the mesh-sharded device state on the base
    cluster: every fused path then routes through `sharded_evaluators`."""
    base = getattr(cluster, "base", cluster)
    base.device_state(sharded=True)


def plan_sharded(cluster, workload, alpha: float = DEFAULT_ALPHA,
                 allow_preempt: bool = True, shortlist=None):
    """`preemption_jax.plan_fused` over the sharded resident state."""
    _sharded_state(cluster)
    return _pj.plan_fused(cluster, workload, alpha, allow_preempt,
                          shortlist=shortlist)


def plan_normal_sharded(cluster, workload):
    """`preemption_jax.plan_normal_fused` over the sharded state."""
    _sharded_state(cluster)
    return _pj.plan_normal_fused(cluster, workload)


def batch_session_sharded(cluster, workloads, alpha: float):
    """`preemption_jax.persistent_batch_session` over the sharded state."""
    _sharded_state(cluster)
    return _pj.persistent_batch_session(cluster, workloads, alpha)


def warmup_sharded(cluster, alpha: float = DEFAULT_ALPHA, batch: int = 8,
                   workloads=None, shortlist=None) -> None:
    """`preemption_jax.warmup_fused` against the sharded jit variants."""
    _sharded_state(cluster)
    _pj.warmup_fused(cluster, alpha, batch, workloads, shortlist=shortlist)


@register_engine("imp_sharded", batched=True, needs_alpha=True,
                 fused_filter=True, fused_place=True, plan_fn=plan_sharded,
                 normal_fn=plan_normal_sharded,
                 batch_factory=batch_session_sharded,
                 warmup_fn=warmup_sharded, supports_shortlist=True)
def source_candidates_sharded(cluster, workload, nodes=None,
                              alpha: float = DEFAULT_ALPHA,
                              shortlist=None):
    """``imp_batched`` semantics, mesh-sharded state: same fused dispatch
    chain, node axis split across every local device.  The shortlist
    prescreen runs shard-local; only the top-K gather and the argmax
    chain cross shards."""
    _sharded_state(cluster)
    return _pj.source_candidates_fused(cluster, workload, nodes,
                                       alpha=alpha, shortlist=shortlist)


# full-sweep parity oracle (see ``imp_batched_full``)
register_engine("imp_sharded_full", batched=True, needs_alpha=True,
                fused_filter=True, fused_place=True, plan_fn=plan_sharded,
                normal_fn=plan_normal_sharded,
                batch_factory=batch_session_sharded,
                warmup_fn=warmup_sharded)(source_candidates_sharded)
