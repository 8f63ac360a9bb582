"""GPipe-style pipeline parallelism over a "stage" mesh axis.

``pipeline_apply`` runs a stage function over microbatches with the classic
GPipe schedule: at step t, stage s processes microbatch (t - s); activations
move stage→stage via ``ppermute``.  The whole schedule is a ``lax.scan`` so
reverse-mode autodiff yields the standard 1F1B-equivalent backward wave for
free (grad of ppermute is the reversed ppermute).

Bubble fraction is the usual (S-1)/(M+S-1); stages compute during bubbles on
zero inputs and the outputs are masked, which keeps the schedule branch-free
(TPU-friendly) at the cost of the bubble FLOPs.

Used via shard_map over a ("stage", ...) mesh; see tests/test_pipeline.py
for the executable 4-stage example (forward equivalence + gradient match
against the unpipelined stack).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(stage_fn, stage_params, microbatches, *, n_stages: int,
                   axis: str = "stage"):
    """Run inside shard_map(..., axis_names={axis}).

    stage_fn: (stage_params, x) -> y       (one stage's layer stack)
    stage_params: THIS stage's parameter shard (leading stage axis stripped)
    microbatches: [M, mb, ...] — identical on every stage; only stage 0
        consumes it (others ignore their copy).
    Returns [M, mb, ...]: the last stage's outputs per microbatch (valid on
    the last stage; other stages return zeros — combine with psum or slice
    outside).
    """
    M = microbatches.shape[0]
    s = jax.lax.axis_index(axis)
    T = M + n_stages - 1
    x_shape = microbatches.shape[1:]
    fwd_perm = [(i, i + 1) for i in range(n_stages - 1)]

    def step(carry, t):
        buf = carry                                    # [mb, ...] held input
        mb_idx = t - s                                 # microbatch this stage
        active = (mb_idx >= 0) & (mb_idx < M)
        y = stage_fn(stage_params, buf)
        y = jnp.where(active, y, jnp.zeros_like(y))
        # ship to the next stage; stage 0 picks up the next microbatch
        shipped = jax.lax.ppermute(y, axis, fwd_perm)
        nxt = jnp.clip(t + 1, 0, M - 1)
        from_feed = microbatches[nxt]
        buf_next = jnp.where(s == 0, from_feed, shipped)
        # last stage emits y for microbatch (t - (S-1)) when valid
        out_idx = t - (n_stages - 1)
        emit = jnp.where((s == n_stages - 1) & (out_idx >= 0), 1.0, 0.0)
        return buf_next, y * emit.astype(y.dtype)

    buf0 = jnp.where(s == 0, microbatches[0],
                     jnp.zeros(x_shape, microbatches.dtype))
    _, ys = jax.lax.scan(step, buf0, jnp.arange(T))
    # ys: [T, mb, ...]; last stage's valid outputs are at t = S-1 .. S-1+M
    return ys[n_stages - 1:]


def make_pipelined_fn(stage_fn, mesh: Mesh, n_stages: int,
                      axis: str = "stage"):
    """shard_map wrapper: stage-stacked params [S, ...] + microbatches in,
    last-stage outputs [M, mb, ...] out (replicated via psum)."""

    def inner(params_stacked, microbatches):
        my_params = jax.tree.map(lambda p: p[0], params_stacked)
        outs = pipeline_apply(stage_fn, my_params, microbatches,
                              n_stages=n_stages, axis=axis)
        # only the last stage holds real outputs; make them global
        return jax.lax.psum(outs, axis)

    return jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        axis_names={axis},
    )
