"""Blocked (flash) causal GQA attention — Pallas TPU kernel.

The train/prefill compute hot spot.  Tiling per DESIGN.md §3: the grid is
(batch, q_head, q_block); each step streams K/V blocks of the matching KV
head through VMEM with running-max/denominator softmax in fp32, so the
[Sq, Sk] score matrix never materializes in HBM.  Causal + sliding-window
masking prunes K blocks entirely outside the window (the loop bound is
computed per q_block, not masked per-element).

MXU alignment: block_q × head_dim and block_k × head_dim tiles with
block_q = block_k = 128 by default (multiples of the 128-lane MXU).
Validated against ``ref.mha_ref``; ``interpret=None`` (the default) runs the
Mosaic interpreter iff the backend is not a TPU, as the other kernels do.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .topo_score import _interpret_default

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, block_q: int, block_k: int,
                  sk: int, causal: bool, window: int | None, scale: float):
    qi = pl.program_id(2)
    q = q_ref[...].astype(jnp.float32) * scale          # [block_q, d]

    q_start = qi * block_q
    # K-block range actually needed by this q block
    if causal:
        hi = jnp.minimum(sk, q_start + block_q)
    else:
        hi = sk
    lo = 0
    if window is not None:
        lo = jnp.maximum(0, q_start - (window - 1))
        lo = (lo // block_k) * block_k

    m = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((block_q, 1), jnp.float32)
    acc = jnp.zeros((block_q, q_ref.shape[-1]), jnp.float32)

    def body(kb, carry):
        m, l, acc = carry
        k_start = kb * block_k
        k = k_ref[pl.ds(k_start, block_k), :].astype(jnp.float32)  # [bk, d]
        v = v_ref[pl.ds(k_start, block_k), :].astype(jnp.float32)
        s = q @ k.T                                     # [block_q, block_k]
        rows = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = cols < sk
        if causal:
            mask &= cols <= rows
        if window is not None:
            mask &= (rows - cols) < window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = alpha * acc + p @ v
        return m_new, l_new, acc_new

    n_blocks = pl.cdiv(hi - lo, block_k)
    m, l, acc = jax.lax.fori_loop(
        lo // block_k, lo // block_k + n_blocks, body, (m, l, acc))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def flash_attention(
    q: jnp.ndarray,              # [B, H, Sq, d]
    k: jnp.ndarray,              # [B, K, Sk, d]
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jnp.ndarray:
    if interpret is None:
        interpret = _interpret_default()
    B, H, Sq, d = q.shape
    K = k.shape[1]
    Sk = k.shape[2]
    G = H // K
    assert H % K == 0

    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    sq_pad = -(-Sq // block_q) * block_q
    sk_pad = -(-Sk // block_k) * block_k
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, sq_pad - Sq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, sk_pad - Sk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, sk_pad - Sk), (0, 0)))

    grid = (B, H, sq_pad // block_q)
    kernel = partial(_flash_kernel, block_q=block_q, block_k=block_k, sk=Sk,
                     causal=causal, window=window, scale=d ** -0.5)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, block_q, d),
                         lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((None, None, sk_pad, d),
                         lambda b, h, i: (b, h // G, 0, 0)),
            pl.BlockSpec((None, None, sk_pad, d),
                         lambda b, h, i: (b, h // G, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, block_q, d),
                               lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, sq_pad, d), q.dtype),
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :, :Sq]
