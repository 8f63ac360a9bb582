"""Feed-forward layers: gated MLP (SwiGLU/GeGLU), plain MLP, and top-k MoE.

The MoE uses sort-based capacity dispatch (TPU-friendly: batched per-expert
matmuls on dense [E, C, d] buffers, no ragged ops): tokens are argsorted by
expert id, placed into per-expert capacity slots, processed with one batched
einsum per projection, and combined with their router gates.  Tokens beyond
an expert's capacity are dropped (standard capacity-factor semantics).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import (ACTIVATIONS, ModelConfig, constrain_spec, dense_init,
                     split_keys)


# ---------------------------------------------------------------------------------
# dense MLP
# ---------------------------------------------------------------------------------

def mlp_init(key, cfg: ModelConfig, d_ff: int | None = None) -> dict:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ks = split_keys(key, 3)
    if cfg.act == "relu":  # non-gated (seamless / classic transformer)
        return {
            "w_in": dense_init(ks[0], (d, f)),
            "w_out": dense_init(ks[1], (f, d)),
        }
    return {
        "w_gate": dense_init(ks[0], (d, f)),
        "w_up": dense_init(ks[1], (d, f)),
        "w_down": dense_init(ks[2], (f, d)),
    }


def mlp_apply(p, cfg: ModelConfig, x):
    cd = cfg.compute_dtype
    act = ACTIVATIONS[cfg.act]
    if "w_in" in p:
        h = act(jnp.einsum("BSD,DF->BSF", x, p["w_in"].astype(cd)))
        return jnp.einsum("BSF,FD->BSD", h, p["w_out"].astype(cd))
    g = act(jnp.einsum("BSD,DF->BSF", x, p["w_gate"].astype(cd)))
    u = jnp.einsum("BSD,DF->BSF", x, p["w_up"].astype(cd))
    return jnp.einsum("BSF,FD->BSD", g * u, p["w_down"].astype(cd))


# ---------------------------------------------------------------------------------
# mixture of experts
# ---------------------------------------------------------------------------------

def moe_init(key, cfg: ModelConfig) -> dict:
    assert cfg.moe is not None
    d, f, E = cfg.d_model, cfg.d_ff, cfg.moe.num_experts
    ks = split_keys(key, 4)
    return {
        "router": dense_init(ks[0], (d, E)),
        "w_gate": dense_init(ks[1], (E, d, f)),
        "w_up": dense_init(ks[2], (E, d, f)),
        "w_down": dense_init(ks[3], (E, f, d)),
    }


def moe_capacity(cfg: ModelConfig, num_tokens: int) -> int:
    moe = cfg.moe
    c = int(num_tokens * moe.top_k / moe.num_experts * moe.capacity_factor)
    return max(8, -(-c // 8) * 8)  # round up to a multiple of 8 for TPU lanes


def moe_apply(p, cfg: ModelConfig, x):
    """x: [B, S, d] -> [B, S, d].  Returns (out, aux) with load-balance stats."""
    if cfg.moe.dispatch == "per_sequence" and x.shape[0] > 1:
        # data-local dispatch: sort/capacity buffers never cross the batch
        # sharding (tokens of one sequence live on one data shard)
        out, aux = jax.vmap(lambda xb: _moe_tokens(p, cfg, xb[None]))(x)
        return out[:, 0], jax.tree.map(jnp.mean, aux)
    if cfg.moe.dispatch == "shard_map":
        from jax.sharding import PartitionSpec as P

        from .common import get_batch_axes, get_mesh

        axes = get_batch_axes()
        if axes and x.shape[0] > 1:
            # manual island over the batch axes: dispatch/sort/capacity math
            # never crosses data shards; the model axis stays auto (GSPMD
            # shards the expert einsums by d_ff as usual).  Requires expert
            # weights replicated over data (cfg.moe_zero1).
            def body(p_, xb):
                out, aux = _moe_tokens(p_, cfg, xb)
                aux = jax.tree.map(lambda a: jax.lax.pmean(a, axes), aux)
                return out, aux

            fn = jax.shard_map(
                body,
                mesh=get_mesh(),
                in_specs=(jax.tree.map(lambda _: P(), p),
                          P(axes, None, None)),
                out_specs=(P(axes, None, None), P()),
                axis_names=set(axes),
            )
            return fn(p, x)
    return _moe_tokens(p, cfg, x)


def _moe_tokens(p, cfg: ModelConfig, x):
    moe = cfg.moe
    cd = cfg.compute_dtype
    B, S, d = x.shape
    T = B * S
    E, k = moe.num_experts, moe.top_k
    C = moe_capacity(cfg, T)
    xf = x.reshape(T, d)

    logits = jnp.einsum("TD,DE->TE", xf.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)            # [T, k]
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    flat_e = expert_idx.reshape(-1)                            # [T*k]
    flat_tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)   # token of each slot
    order = jnp.argsort(flat_e)                                # stable in jax
    sorted_e = flat_e[order]
    sorted_tok = flat_tok[order]

    counts = jnp.bincount(flat_e, length=E)                    # [E]
    excl = jnp.cumsum(counts) - counts                         # exclusive prefix
    pos_in_e = jnp.arange(T * k, dtype=jnp.int32) - excl[sorted_e]
    keep = pos_in_e < C
    e_safe = jnp.where(keep, sorted_e, E)                      # dropped -> dummy row
    pos_safe = jnp.where(keep, pos_in_e, C - 1)

    buf = jnp.zeros((E + 1, C, d), cd)
    buf = buf.at[e_safe, pos_safe].set(xf[sorted_tok].astype(cd))

    act = ACTIVATIONS[cfg.act]
    constrain = (moe.constrain_ffn and cfg.moe.dispatch == "global")
    buf_c = constrain_spec(buf, (None, None, None)) if constrain else buf
    g = act(jnp.einsum("ECD,EDF->ECF", buf_c[:E], p["w_gate"].astype(cd)))
    u = jnp.einsum("ECD,EDF->ECF", buf_c[:E], p["w_up"].astype(cd))
    if constrain:
        # Megatron pattern: intermediates live sharded on the model axis,
        # the psum happens once on the (d-sized) down-projection output
        g = constrain_spec(g, (None, None, "model"))
        u = constrain_spec(u, (None, None, "model"))
    out_buf = jnp.einsum("ECF,EFD->ECD", g * u, p["w_down"].astype(cd))
    if constrain:
        out_buf = constrain_spec(out_buf, (None, None, None))
    out_buf = jnp.concatenate([out_buf, jnp.zeros((1, C, d), cd)], axis=0)

    gathered = out_buf[e_safe, pos_safe] * keep[:, None].astype(cd)
    inv = jnp.argsort(order)
    y_flat = gathered[inv]                                     # back to [T*k, d]
    y = (y_flat.reshape(T, k, d)
         * gate_vals.reshape(T, k, 1).astype(cd)).sum(axis=1)

    # aux: load-balancing loss terms (Switch-style) + drop fraction
    me = jnp.mean(probs, axis=0)                               # mean router prob
    ce = counts.astype(jnp.float32) / (T * k)                  # token fraction
    aux = {
        "load_balance_loss": E * jnp.sum(me * ce),
        "dropped_fraction": 1.0 - jnp.mean(keep.astype(jnp.float32)),
    }
    return y.reshape(B, S, d), aux
