"""Shared model building blocks (pure JAX, functional param-dict style).

Every layer is a pair of functions: ``*_init(key, ...) -> params`` (fp32
pytree of jnp arrays) and an apply function taking (params, x, ...).  Repeated
transformer blocks are stacked along a leading layer axis and executed with
``lax.scan`` so the lowered HLO stays one-block-sized at any depth.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

Params = Any  # nested dict pytree


# ---------------------------------------------------------------------------------
# Model configuration
# ---------------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    capacity_factor: float = 1.25
    # "global": one dispatch over all tokens (baseline; the argsort and
    #   capacity buffers span data shards -> cross-shard collectives).
    # "per_sequence": dispatch within each sequence (vmapped over batch; the
    #   sort/buffers stay data-local — §Perf hillclimb for collective-bound
    #   MoE training).
    dispatch: str = "global"
    # Megatron-style anchors on the expert FFN intermediates (g/u sharded on
    # the model axis, psum deferred to the down-projection output) — §Perf
    # lever for GSPMD backward partitioning (global dispatch only).
    constrain_ffn: bool = False


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                  # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv: int
    d_ff: int
    vocab: int
    head_dim: int | None = None          # default d_model // n_heads
    qkv_bias: bool = False               # qwen1.5 / qwen2
    qk_norm: bool = False                # qwen3
    swa_window: int | None = None        # mixtral sliding-window
    local_window: int | None = None      # recurrentgemma local attention
    moe: MoEConfig | None = None
    act: str = "silu"                    # silu (swiglu) | gelu (geglu) | relu
    tie_embeddings: bool = False
    scale_embed: bool = False            # gemma-style sqrt(d) embedding scale
    rope_theta: float = 10_000.0
    rms_eps: float = 1e-6
    # enc-dec split (seamless): n_layers is the per-stack depth
    enc_layers: int = 0
    dec_layers: int = 0
    # hybrid pattern (recurrentgemma): period-3 [rec, rec, attn]
    attn_pattern: str = "all"            # all | griffin_1_2 | rwkv
    rnn_width: int | None = None         # rg-lru recurrence width
    conv_kernel: int = 4
    # modality frontend stub (vlm: patch embeddings; audio: frame embeddings)
    frontend: str | None = None          # None | patch | frames
    frontend_len: int = 256              # prefix length supplied by the stub
    prefix_lm: bool = False              # paligemma: bidirectional prefix mask
    # numerics
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    # activation rematerialization for the layer scan body:
    #   none | full (save nothing) | dots (save non-batch matmul outputs)
    remat: str = "full"
    # performance levers (see EXPERIMENTS.md §Perf):
    # q-chunked attention for long-sequence train/prefill (XLA-level flash —
    # scores never exceed [B, H, chunk, S_k]); None = unchunked baseline
    attn_chunk_q: int | None = None
    # sequence parallelism: residual stream sharded over the model axis
    # between blocks (all-reduce -> all-gather/reduce-scatter in bf16)
    seq_shard: bool = False
    # ZeRO-1 for expert weights: params replicated over the data axis (only
    # optimizer states stay data-sharded), removing per-layer weight gathers
    # and GSPMD's backward activation psums at the cost of replicated
    # expert params in HBM — §Perf lever for collective-bound MoE training
    moe_zero1: bool = False
    # ZeRO-1 for ALL weights (dense archs): same trade as moe_zero1 —
    # bf16 params replicated over data (TP-sharded only), optimizer states
    # stay fully sharded; per-layer FSDP all-gathers disappear
    zero1: bool = False

    @property
    def hd(self) -> int:
        return self.head_dim if self.head_dim is not None else self.d_model // self.n_heads

    @property
    def is_encdec(self) -> bool:
        return self.enc_layers > 0

    def validate(self) -> None:
        assert self.n_heads % max(self.n_kv, 1) == 0 or self.n_kv == 0


# ---------------------------------------------------------------------------------
# Initializers / primitive layers
# ---------------------------------------------------------------------------------

def dense_init(key, shape, scale: float | None = None, dtype=jnp.float32):
    """Truncated-normal fan-in init (shape[..., in, out] semantics by caller)."""
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = scale if scale is not None else fan_in ** -0.5
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape) * std).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype=jnp.float32):
    # fan-in scale: unit-RMS hidden states then produce O(1) logits; gemma-style
    # configs recover O(1) activations at the input via the sqrt(d) embed scale.
    return (jax.random.normal(key, (vocab, d)) * d ** -0.5).astype(dtype)


def rmsnorm_init(d: int, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(params, x, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * params["scale"].astype(jnp.float32)).astype(dt)


def layernorm_init(d: int, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm(params, x, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    return (x * params["scale"].astype(jnp.float32)
            + params["bias"].astype(jnp.float32)).astype(dt)


ACTIVATIONS: dict[str, Callable] = {
    "silu": jax.nn.silu,
    "gelu": lambda x: jax.nn.gelu(x, approximate=True),
    "relu": jax.nn.relu,
}


# ---------------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------------

def rope_freqs(hd: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: [..., S, H, hd]; positions: broadcastable to [..., S]."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                        # [hd/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., S, hd/2]
    sin = jnp.sin(angles)[..., None, :]                  # [..., S, 1, hd/2]
    cos = jnp.cos(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------------
# Misc
# ---------------------------------------------------------------------------------

def split_keys(key, n: int):
    return list(jax.random.split(key, n))


# ---------------------------------------------------------------------------------
# activation sharding constraints
#
# GSPMD needs anchors to keep the batch axis sharded through the network
# (otherwise it may treat the FSDP axis as a contraction split and replicate
# activations).  The launcher declares the batch mesh axes before tracing;
# model code calls constrain_batch() at block boundaries.
# ---------------------------------------------------------------------------------

_BATCH_AXES: tuple[str, ...] | None = None
_MESH = None


def set_batch_axes(axes: tuple[str, ...] | None, mesh=None) -> None:
    global _BATCH_AXES, _MESH
    _BATCH_AXES = tuple(axes) if axes else None
    _MESH = mesh


def get_batch_axes() -> tuple[str, ...] | None:
    return _BATCH_AXES


def get_mesh():
    return _MESH


def constrain_batch(x: jnp.ndarray) -> jnp.ndarray:
    """Anchor: dim 0 sharded over the declared batch axes, rest unconstrained."""
    if _BATCH_AXES is None:
        return x
    from jax.sharding import PartitionSpec as P

    spec = P(_BATCH_AXES, *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, spec)


def constrain_spec(x: jnp.ndarray, axes: tuple) -> jnp.ndarray:
    """Anchor with an explicit per-dim axis tuple ('batch' expands to the
    declared batch axes); no-op outside a sharded run."""
    if _BATCH_AXES is None:
        return x
    from jax.sharding import PartitionSpec as P

    spec = P(*[(_BATCH_AXES if a == "batch" else a) for a in axes])
    return jax.lax.with_sharding_constraint(x, spec)


def constrain_hidden(x: jnp.ndarray, cfg: "ModelConfig") -> jnp.ndarray:
    """Residual-stream anchor between blocks: batch-sharded, plus
    sequence-sharded over the model axis when cfg.seq_shard (SP)."""
    if cfg.seq_shard and x.ndim >= 3 and x.shape[1] > 1:
        return constrain_spec(x, ("batch", "model") + (None,) * (x.ndim - 2))
    return constrain_batch(x)


def maybe_remat(fn: Callable, cfg: "ModelConfig") -> Callable:
    """Wrap a scan body with jax.checkpoint per the config's remat policy."""
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    else:
        policy = jax.checkpoint_policies.nothing_saveable
    return jax.checkpoint(fn, policy=policy)


def stack_layers(init_fn: Callable, key, n: int) -> Params:
    """Initialize n identical blocks and stack each leaf along axis 0."""
    keys = jax.random.split(key, n)
    layers = [init_fn(k) for k in keys]
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *layers)


def count_params(params: Params) -> int:
    return sum(p.size for p in jax.tree.leaves(params))
