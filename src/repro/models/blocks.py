"""Block registry: one (init, train-apply, decode-apply, cache-init)
set per block kind.

Kinds (``ModelConfig.layer_pattern`` characters):
  G global attention + MLP        L sliding-window attention + MLP
  R RG-LRU recurrent + MLP        W RWKV6 time-mix + channel-mix
  C self-attn + cross-attn + MLP (whisper decoder / llama-vision)

Every block is pre-norm residual.  MLP is MoE when the config has
experts, else (gated) dense.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import mla
from repro.models import moe as moe_mod
from repro.models import recurrent as rec
from repro.models.common import (ModelConfig, Params, apply_norm, dense_init,
                                 init_norm, split_keys)
from repro.models.sharding import constrain


# ----------------------------------------------------------------------
# Dense MLP
# ----------------------------------------------------------------------
def init_mlp(cfg: ModelConfig, key) -> Params:
    d, ff = cfg.d_model, cfg.d_ff
    ks = split_keys(key, 3)
    p = {"wi": dense_init(ks[0], (d, ff), cfg.dtype),
         "wo": dense_init(ks[1], (ff, d), cfg.dtype, in_axis_size=ff)}
    if cfg.mlp_gated:
        p["wg"] = dense_init(ks[2], (d, ff), cfg.dtype)
    return p


def mlp_apply(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    h = jnp.einsum("bsd,df->bsf", x, p["wi"])
    if cfg.mlp_gated:
        g = jnp.einsum("bsd,df->bsf", x, p["wg"])
        h = h * jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype)
    else:
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(h.dtype)
    h = constrain(h, "batch", None, "tensor")
    return jnp.einsum("bsf,fd->bsd", h, p["wo"])


def _ffn_init(cfg: ModelConfig, key, moe: bool = True) -> Params:
    if cfg.num_experts and moe:
        return {"moe": moe_mod.init_moe(cfg, key)}
    return {"mlp": init_mlp(cfg, key)}


def _ffn_apply(cfg: ModelConfig, p: Params, x: jax.Array) -> tuple[jax.Array, jax.Array]:
    if "moe" in p:
        return moe_mod.moe_mlp(cfg, p["moe"], x)
    return mlp_apply(cfg, p["mlp"], x), jnp.zeros((), jnp.float32)


# ----------------------------------------------------------------------
# Block init
# ----------------------------------------------------------------------
def init_block(cfg: ModelConfig, kind: str, key, moe: bool = True) -> Params:
    """``moe=False`` gives a block the dense ``d_ff`` MLP in a model with
    experts (the leading ``first_k_dense`` layers)."""
    ks = split_keys(key, 4)
    if kind in ("G", "L"):
        mixer = mla.init_mla(cfg, ks[0]) if cfg.is_mla \
            else attn.init_attention(cfg, ks[0])
        return {"norm1": init_norm(cfg), "attn": mixer,
                "norm2": init_norm(cfg), **_ffn_init(cfg, ks[1], moe)}
    if kind == "C":
        return {"norm1": init_norm(cfg), "attn": attn.init_attention(cfg, ks[0]),
                "norm_x": init_norm(cfg),
                "xattn": attn.init_attention(cfg, ks[2]),
                "norm2": init_norm(cfg), **_ffn_init(cfg, ks[1])}
    if kind == "R":
        return {"norm1": init_norm(cfg), "rglru": rec.init_rglru_block(cfg, ks[0]),
                "norm2": init_norm(cfg), **_ffn_init(cfg, ks[1])}
    if kind == "W":
        return {"norm1": init_norm(cfg),
                "time_mix": rec.init_rwkv_time_mix(cfg, ks[0]),
                "norm2": init_norm(cfg),
                "channel_mix": rec.init_rwkv_channel_mix(cfg, ks[1])}
    raise ValueError(f"unknown block kind {kind!r}")


# ----------------------------------------------------------------------
# Train / prefill (no cache)
# ----------------------------------------------------------------------
def apply_block(cfg: ModelConfig, kind: str, p: Params, x: jax.Array,
                positions: jax.Array, encoder_out: jax.Array | None = None,
                ) -> tuple[jax.Array, jax.Array]:
    """Returns (x, moe_aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    if kind in ("G", "L", "C"):
        h = apply_norm(cfg, p["norm1"], x)
        window = cfg.sliding_window if kind == "L" else None
        if cfg.is_mla:
            x = x + mla.mla_fwd(cfg, p["attn"], h, positions)
        else:
            x = x + attn.attention_fwd(cfg, p["attn"], h, positions,
                                       causal=True, window=window)
        if kind == "C":
            h = apply_norm(cfg, p["norm_x"], x)
            x = x + attn.attention_fwd(cfg, p["xattn"], h, positions,
                                       kv_src=encoder_out, use_rope=False)
        h = apply_norm(cfg, p["norm2"], x)
        y, aux = _ffn_apply(cfg, p, h)
        x = x + y
    elif kind == "R":
        h = apply_norm(cfg, p["norm1"], x)
        y, _ = rec.rglru_block(cfg, p["rglru"], h)
        x = x + y
        h = apply_norm(cfg, p["norm2"], x)
        y, aux = _ffn_apply(cfg, p, h)
        x = x + y
    elif kind == "W":
        h = apply_norm(cfg, p["norm1"], x)
        y, _ = rec.rwkv_time_mix(cfg, p["time_mix"], h)
        x = x + y
        h = apply_norm(cfg, p["norm2"], x)
        y, _ = rec.rwkv_channel_mix(cfg, p["channel_mix"], h)
        x = x + y
    else:
        raise ValueError(kind)
    x = constrain(x, "batch", None, None)
    return x, aux


# ----------------------------------------------------------------------
# Decode (serve_step): one token + cache
# ----------------------------------------------------------------------
def init_block_cache(cfg: ModelConfig, kind: str, batch: int, seq_len: int,
                     ) -> Params:
    if cfg.is_mla:
        raise NotImplementedError(
            f"{cfg.name}: latent attention (MLA) runs training and prefill "
            f"only; it has no decode cache")
    if kind == "G":
        return attn.init_kv_cache(cfg, batch, seq_len)
    if kind == "L":
        return attn.init_kv_cache(cfg, batch, seq_len, window=cfg.sliding_window)
    if kind == "C":
        return attn.init_kv_cache(cfg, batch, seq_len)   # self-attn cache only
    if kind == "R":
        W, kw = cfg.rnn_size, cfg.conv1d_width
        return {"h": jnp.zeros((batch, W), jnp.float32),
                "conv": jnp.zeros((batch, kw - 1, W), cfg.dtype)}
    if kind == "W":
        H, hd = rec.rwkv_heads(cfg), rec.RWKV_HEAD_DIM
        d = cfg.d_model
        return {"S": jnp.zeros((batch, H, hd, hd), jnp.float32),
                "x_prev_tm": jnp.zeros((batch, d), cfg.dtype),
                "x_prev_cm": jnp.zeros((batch, d), cfg.dtype)}
    raise ValueError(kind)


def decode_block(cfg: ModelConfig, kind: str, p: Params, x: jax.Array,
                 cache: Params, pos: jax.Array,
                 encoder_out: jax.Array | None = None,
                 seq_axis: str | None = None,
                 ) -> tuple[jax.Array, Params]:
    """x: (B, 1, d) -> (x, new_cache)."""
    if kind in ("G", "L", "C"):
        h = apply_norm(cfg, p["norm1"], x)
        window = cfg.sliding_window if kind == "L" else None
        y, new_cache = attn.decode_attention(
            cfg, p["attn"], h, cache, pos, window=window,
            seq_axis=seq_axis if kind == "G" else None)
        x = x + y
        if kind == "C":
            h = apply_norm(cfg, p["norm_x"], x)
            posb = jnp.broadcast_to(pos[None, None], (x.shape[0], 1))
            x = x + attn.attention_fwd(cfg, p["xattn"], h, posb,
                                       kv_src=encoder_out, use_rope=False)
        h = apply_norm(cfg, p["norm2"], x)
        y, _ = _ffn_apply(cfg, p, h)
        x = x + y
    elif kind == "R":
        h = apply_norm(cfg, p["norm1"], x)
        y, new_cache = rec.rglru_block(cfg, p["rglru"], h, state=cache)
        x = x + y
        h = apply_norm(cfg, p["norm2"], x)
        y, _ = _ffn_apply(cfg, p, h)
        x = x + y
    elif kind == "W":
        h = apply_norm(cfg, p["norm1"], x)
        y, tm_state = rec.rwkv_time_mix(
            cfg, p["time_mix"], h,
            state={"S": cache["S"], "x_prev": cache["x_prev_tm"]})
        x = x + y
        h = apply_norm(cfg, p["norm2"], x)
        y, x_prev_cm = rec.rwkv_channel_mix(cfg, p["channel_mix"], h,
                                            x_prev=cache["x_prev_cm"])
        x = x + y
        new_cache = {"S": tm_state["S"], "x_prev_tm": tm_state["x_prev"],
                     "x_prev_cm": x_prev_cm}
    else:
        raise ValueError(kind)
    return x, new_cache
