"""Shared model-definition machinery: the generic ArchConfig and primitives.

One configuration dataclass describes every assigned architecture (dense,
MoE, SSM, hybrid, enc-dec audio, VLM).  Block kinds are composed via
``block_pattern`` which is cycled across the layer stack; parameters for a
homogeneous stack are *stacked along a leading layer axis* and executed with
``jax.lax.scan`` so tracing/compile cost is O(pattern), not O(n_layers) —
essential for the 126-layer 405B dry-run on a CPU host.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

__all__ = ["ArchConfig", "rms_norm", "apply_rope", "rope_angles", "softcap", "uniform_init"]


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str  # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int | None = None  # default d_model // n_heads

    # layer composition: cycled across layers; len must divide n_layers
    block_pattern: tuple[str, ...] = ("attn",)

    # attention details
    rope_theta: float = 10000.0
    sliding_window: int | None = None  # used by "attn_local" blocks
    attn_softcap: float | None = None  # gemma2 attention-logit soft capping
    final_softcap: float | None = None  # gemma2 output-logit soft capping
    qk_norm: bool = False  # qwen3 per-head q/k RMSNorm

    # MoE
    n_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0
    dense_residual: bool = False  # arctic: dense MLP in parallel with MoE
    capacity_factor: float = 1.25

    # SSM (Mamba2 / xLSTM)
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    conv_width: int = 4
    conv_bias: bool = False  # mamba2: a bias on the depthwise causal conv
    shared_attn_every: int = 0  # zamba2: shared attention block cadence

    # encoder-decoder / multimodal
    encoder_layers: int = 0  # whisper encoder depth
    cross_attn_every: int = 0  # vlm: every k-th layer is a cross-attn block
    frontend: str | None = None  # "audio" | "vision" (stubbed embeddings)
    frontend_seq: int = 0  # number of frames / image patches
    frontend_dim: int = 0  # embedding dim delivered by the stubbed frontend
    scale_embed: bool = False  # gemma2: h *= sqrt(d_model)

    # granite-4.0-h scalars; None leaves the op out of the program
    position_embedding: str = "rope"  # "rope" | "nope" (no positional encoding)
    attn_scale: float | None = None  # softmax scale on q.k (None: hd^-1/2)
    embed_multiplier: float | None = None  # h = m * embed[tok]
    residual_multiplier: float | None = None  # h += m * block(h)
    logit_divisor: float | None = None  # logits = head(h) / m

    # numerics
    param_dtype: Any = jnp.bfloat16
    norm_eps: float = 1e-6
    act: str = "silu"
    tie_embeddings: bool = True

    # federated execution (DESIGN.md section 3)
    round_mode: str = "client_parallel"  # or "cohort_sequential"
    long_context_ok: bool = False  # sub-quadratic decode supported
    remat: str = "full"  # "full" | "none" — checkpoint the layer-scan body
    attn_impl: str = "einsum"  # "einsum" | "chunked" (online-softmax over KV
    # blocks — the jnp realization of kernels/flash_attention; O(S) memory)
    moe_impl: str = "dense"  # "dense" (GSPMD scatter dispatch) | "a2a"
    # (shard_map all-to-all dispatch; requires a mesh context + tokens
    # sharded (batch->data, seq->model); cohort_sequential archs only)
    mlstm_impl: str = "scan"  # "scan" (per-step cell) | "chunked"
    mlstm_chunk: int = 128  # chunk length for the chunked mLSTM
    slstm_segment: int = 0  # >0: segment-remat the sLSTM scan (saves only
    # every segment-th state for backward; recomputes within segments)
    # (chunkwise-parallel stabilized form: MXU GEMMs per chunk, states only
    # at chunk boundaries — the TPU-native mLSTM, see xlstm.mlstm_chunked)

    # provenance
    source: str = ""

    @property
    def hd(self) -> int:
        return self.head_dim if self.head_dim is not None else self.d_model // self.n_heads

    @property
    def q_groups(self) -> int:
        return self.n_heads // self.n_kv_heads

    def pattern_repeats(self) -> int:
        assert self.n_layers % len(self.block_pattern) == 0, (
            f"{self.name}: pattern {self.block_pattern} must divide {self.n_layers} layers"
        )
        return self.n_layers // len(self.block_pattern)

    def param_count(self, params) -> int:
        return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))

    def reduced(self, **overrides) -> "ArchConfig":
        """A tiny same-family variant for CPU smoke tests."""
        n_pat = len(self.block_pattern)
        small = dict(
            n_layers=max(n_pat, 2 if n_pat == 1 else n_pat),
            d_model=min(self.d_model, 128),
            n_heads=min(self.n_heads, 4),
            n_kv_heads=min(self.n_kv_heads, 2),
            d_ff=min(self.d_ff, 256) if self.d_ff else 0,
            vocab=min(self.vocab, 512),
            head_dim=32 if self.head_dim else None,
            n_experts=min(self.n_experts, 4) if self.n_experts else 0,
            top_k=min(self.top_k, 2) if self.top_k else 0,
            moe_d_ff=min(self.moe_d_ff, 128) if self.moe_d_ff else 0,
            # dropless at test scale: capacity >= E/k covers the worst-case
            # routing so prefill+decode agree exactly with the full forward
            capacity_factor=8.0 if self.n_experts else self.capacity_factor,
            ssm_state=min(self.ssm_state, 16) if self.ssm_state else 0,
            ssm_head_dim=16 if self.ssm_state else self.ssm_head_dim,
            encoder_layers=min(self.encoder_layers, 2) if self.encoder_layers else 0,
            frontend_seq=min(self.frontend_seq, 16) if self.frontend_seq else 0,
            frontend_dim=min(self.frontend_dim, 128) if self.frontend_dim else 0,
            sliding_window=min(self.sliding_window, 64) if self.sliding_window else None,
            param_dtype=jnp.float32,
            name=self.name + "-reduced",
        )
        small.update(overrides)
        return dataclasses.replace(self, **small)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dt)


def softcap(x: jax.Array, cap: float | None) -> jax.Array:
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


def rope_angles(positions: jax.Array, head_dim: int, theta: float) -> tuple[jax.Array, jax.Array]:
    """cos/sin tables for rotary embeddings at given integer positions."""
    freqs = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (..., hd/2)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (..., seq, heads, head_dim); cos/sin: (seq, head_dim/2)."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    while cos.ndim < x1.ndim:
        cos = cos[..., None, :] if cos.ndim == x1.ndim - 1 else cos[None]
        sin = sin[..., None, :] if sin.ndim == x1.ndim - 1 else sin[None]
    dt = x.dtype
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([x1f * cos - x2f * sin, x2f * cos + x1f * sin], axis=-1)
    return out.astype(dt)


def uniform_init(key: jax.Array, shape, dtype, scale: float | None = None) -> jax.Array:
    fan_in = shape[0] if len(shape) >= 2 else max(shape[-1], 1)
    if scale is None:
        scale = 1.0 / math.sqrt(fan_in)
    return (jax.random.uniform(key, shape, jnp.float32, -1.0, 1.0) * scale).astype(dtype)
