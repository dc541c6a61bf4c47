"""Shared model vocabulary: config dataclass, norms, RoPE, init helpers.

One :class:`ModelConfig` describes every assigned architecture.  Layer
heterogeneity (gemma3's 5 local : 1 global, recurrentgemma's 2
recurrent : 1 local-attention, llama-vision's cross-attention every
5th layer) is expressed as a repeating ``layer_pattern`` string; the
transformer scans over *pattern units* so the HLO stays small and the
parameter count stays exact.

Block kind characters:
  ``G`` global self-attention      ``L`` local (sliding-window) self-attention
  ``R`` RG-LRU recurrent block     ``W`` RWKV6 time-mix + channel-mix block
  ``C`` cross-attention block (self-attn + cross-attn + mlp)
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp

Params = Any      # nested dict pytree of jnp arrays


@dataclass(frozen=True)
class ModelConfig:
    name: str
    arch_type: str                     # dense | moe | ssm | hybrid | encdec | vlm | audio
    num_layers: int
    d_model: int
    num_heads: int
    d_ff: int
    vocab_size: int
    num_kv_heads: int | None = None
    head_dim: int | None = None
    qkv_bias: bool = False
    mlp_gated: bool = True             # SwiGLU; False = GELU MLP (whisper)
    norm: str = "rmsnorm"              # rmsnorm | layernorm
    layer_pattern: str = "G"
    sliding_window: int | None = None  # tokens, for 'L' blocks
    rope_theta: float = 10_000.0
    # --- MoE ---
    num_experts: int = 0
    experts_per_token: int = 0
    moe_d_ff: int = 0                  # per-expert hidden dim
    shared_expert_d_ff: int = 0        # fused shared-experts hidden dim
    capacity_factor: float = 1.25
    moe_group_size: int = 512
    first_k_dense: int = 0             # leading layers with a dense d_ff MLP
    norm_topk_prob: bool = True        # renormalize the top-k gates to sum 1
    # --- multi-head latent attention (MLA; kv_lora_rank > 0) ---
    kv_lora_rank: int = 0              # width of the compressed kv latent
    qk_nope_head_dim: int = 0          # per-head q/k width without RoPE
    qk_rope_head_dim: int = 0          # per-head q/k width with RoPE (k shared)
    v_head_dim: int = 0                # per-head value width
    # --- recurrent (R/W blocks) ---
    rnn_width: int = 0                 # RG-LRU recurrence width (0 = d_model)
    conv1d_width: int = 4
    # --- encoder-decoder / VLM ---
    encoder_layers: int = 0
    encoder_seq: int = 0               # e.g. whisper 1500 mel frames
    encoder_d_model: int = 0
    num_image_tokens: int = 0          # VLM stub patch-embedding count
    # --- numerics ---
    dtype: Any = jnp.bfloat16
    logit_dtype: Any = jnp.float32
    tie_embeddings: bool = False
    source: str = ""                   # citation (arXiv / model card)

    # ------------------------------------------------------------------
    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def head_size(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def rnn_size(self) -> int:
        return self.rnn_width or self.d_model

    @property
    def pattern_unit(self) -> str:
        return self.layer_pattern

    @property
    def num_units(self) -> int:
        return (self.num_layers - self.first_k_dense) // len(self.layer_pattern)

    @property
    def remainder_pattern(self) -> str:
        """Layers that do not fill a whole pattern unit (prefix order)."""
        rest = (self.num_layers - self.first_k_dense) % len(self.layer_pattern)
        return self.layer_pattern[:rest]

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def is_subquadratic(self) -> bool:
        """True when no block attends globally over the full sequence,
        or attention-free blocks dominate memory (SSM/hybrid), making
        the 500k-decode shape feasible."""
        return self.arch_type in ("ssm", "hybrid") or "G" not in self.layer_pattern \
            or self.arch_type == "dense" and self.sliding_window is not None

    def validate(self) -> "ModelConfig":
        if self.num_layers < len(self.remainder_pattern):
            raise ValueError("num_layers smaller than pattern remainder")
        if self.num_heads % self.kv_heads:
            raise ValueError(f"{self.name}: num_heads {self.num_heads} not a "
                             f"multiple of kv heads {self.kv_heads}")
        if self.num_experts and not self.experts_per_token:
            raise ValueError("MoE needs experts_per_token")
        if not 0 <= self.first_k_dense < self.num_layers:
            raise ValueError("first_k_dense must leave at least one layer")
        if self.is_mla and not (self.qk_rope_head_dim
                                and self.qk_nope_head_dim and self.v_head_dim):
            raise ValueError("MLA needs qk_nope/qk_rope/v head dims")
        for ch in self.layer_pattern:
            if ch not in "GLRWC":
                raise ValueError(f"unknown block kind {ch!r}")
        return self

    def reduced(self, num_layers: int = 2, d_model: int = 256,
                num_heads: int = 4, d_ff: int = 512, vocab_size: int = 512,
                num_experts: int | None = None, **over) -> "ModelConfig":
        """Smoke-test variant of the same family (spec: 2 layers,
        d_model<=512, <=4 experts)."""
        kv = max(1, min(self.kv_heads, num_heads))
        ne = min(self.num_experts, 4) if num_experts is None else num_experts
        changes: dict[str, Any] = dict(
            name=self.name + "-reduced",
            num_layers=num_layers, d_model=d_model, num_heads=num_heads,
            num_kv_heads=kv if self.num_kv_heads else None,
            head_dim=d_model // num_heads if self.head_dim else None,
            d_ff=d_ff, vocab_size=vocab_size,
            num_experts=ne,
            experts_per_token=min(self.experts_per_token, max(ne, 1)) if ne else 0,
            moe_d_ff=min(self.moe_d_ff, d_ff) if ne else 0,
            shared_expert_d_ff=min(self.shared_expert_d_ff, d_ff),
            rnn_width=min(self.rnn_size, d_model) if self.rnn_width else 0,
            sliding_window=min(self.sliding_window, 64) if self.sliding_window else None,
            encoder_layers=min(self.encoder_layers, 2),
            encoder_seq=min(self.encoder_seq, 64) if self.encoder_seq else 0,
            encoder_d_model=min(self.encoder_d_model, d_model) if self.encoder_d_model else 0,
            num_image_tokens=min(self.num_image_tokens, 16),
            first_k_dense=min(self.first_k_dense, num_layers - 1),
            kv_lora_rank=min(self.kv_lora_rank, d_model // 4),
            qk_nope_head_dim=min(self.qk_nope_head_dim, d_model // num_heads),
            qk_rope_head_dim=min(self.qk_rope_head_dim,
                                 d_model // (2 * num_heads)),
            v_head_dim=min(self.v_head_dim, d_model // num_heads),
            moe_group_size=64,
            dtype=jnp.float32, logit_dtype=jnp.float32,
            # keep one block of each distinct kind so reduced variants
            # still exercise the family's heterogeneity (e.g. "GGGGC"
            # -> "GC", "LLLLLG" -> "LG", "RRL" -> "RL")
            layer_pattern="".join(dict.fromkeys(self.layer_pattern))[:num_layers]
            if len(self.layer_pattern) > num_layers else self.layer_pattern,
        )
        changes.update(over)
        return dataclasses.replace(self, **changes).validate()


# ----------------------------------------------------------------------
# Numerics
# ----------------------------------------------------------------------
def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


def apply_norm(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    if cfg.norm == "rmsnorm":
        return rms_norm(x, p["scale"])
    return layer_norm(x, p["scale"], p["bias"])


def init_norm(cfg: ModelConfig, dtype=jnp.float32) -> Params:
    if cfg.norm == "rmsnorm":
        return {"scale": jnp.zeros((cfg.d_model,), dtype)}
    return {"scale": jnp.ones((cfg.d_model,), dtype),
            "bias": jnp.zeros((cfg.d_model,), dtype)}


# ----------------------------------------------------------------------
# RoPE
# ----------------------------------------------------------------------
def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    freqs = rope_frequencies(x.shape[-1], theta)                 # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    angles = angles[..., None, :]                                # (..., S, 1, hd/2)
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------------
# Init helpers
# ----------------------------------------------------------------------
def dense_init(key, shape, dtype, in_axis_size: int | None = None):
    fan_in = in_axis_size if in_axis_size is not None else shape[0]
    std = 1.0 / math.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def split_keys(key, n: int):
    return list(jax.random.split(key, n))
