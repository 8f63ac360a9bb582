"""The ``imp_sharded`` engine: ``imp_batched`` over a mesh-sharded state.

Beyond the paper: at 10^4–10^5 nodes, even vectorized subset evaluation on one
device dominates scheduling latency.  ``imp_sharded`` installs the cluster's
`DeviceClusterState` over `cluster.cluster_mesh()` (the resident
nodestate/victims/drain tensors `NamedSharding`-split on the node axis over
a 1-D mesh of every local device) and runs `preemption_jax`'s fused entry
points — `plan_fused`, `plan_normal_fused`, `source_candidates_fused` and
the batch sessions — unchanged: each evaluator factory builds its program
for the state's mesh.  Per-node filtering, subset sweeps and class
reductions stay shard-local; only the final Eq. 2 argmax chain (and the
one-row winner gather) crosses shards.  Decisions are bit-identical to
``imp_batched`` (see tests/test_distributed.py).
"""
from __future__ import annotations

from . import preemption_jax as _pj
from .engines import register_engine
from .scoring import DEFAULT_ALPHA


def _sharded_state(cluster) -> None:
    """Idempotently install the mesh-sharded device state on the base
    cluster: every fused path then builds its programs for that mesh."""
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
    """`preemption_jax.warmup_fused` over the sharded state."""
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
