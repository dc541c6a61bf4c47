"""Multi-head latent attention (MLA, DeepSeek-V2, arXiv:2405.04434 §2.1),
for training and prefill: no decode cache.

Keys and values come from one compressed latent per token.  With
``d = d_model``, ``H`` heads, latent width ``r = kv_lora_rank`` and the
per-head widths ``n = qk_nope_head_dim``, ``p = qk_rope_head_dim``,
``v = v_head_dim`` (no query compression, as in DeepSeek-V2-Lite):

* ``q = x W_q``, ``(H, n + p)`` per token; RoPE on its last ``p``;
* ``[c, k_r] = x W_kva``: the latent ``c`` (``r``, RMS-normed) and one
  RoPE key ``k_r`` (``p``) shared by every head;
* ``[k_n, val] = c W_kvb``, ``(H, n + v)``; the key of a head is
  ``[k_n, RoPE(k_r)]``;
* causal softmax attention over ``n + p``-wide scores, ``v``-wide
  values, then ``W_o`` back to ``d``.

Parameters: ``d·H(n+p) + d(r+p) + r·H(n+v) + H·v·d`` matrices plus the
latent's norm scale, which :mod:`repro.core.archcost` counts.

Departures from the published model: plain RoPE at ``rope_theta`` (the
published config scales it with YaRN for long contexts, which changes
no shape), and the softmax scale is ``(n + p)^-1/2`` without YaRN's
``mscale`` correction.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.models.common import (ModelConfig, Params, apply_rope, dense_init,
                                 rms_norm, split_keys)


def init_mla(cfg: ModelConfig, key) -> Params:
    d, H, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    n, p, v = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = split_keys(key, 4)
    return {
        "wq": dense_init(ks[0], (d, H, n + p), cfg.dtype, in_axis_size=d),
        "wkva": dense_init(ks[1], (d, r + p), cfg.dtype, in_axis_size=d),
        "kv_norm": {"scale": jnp.zeros((r,), jnp.float32)},
        "wkvb": dense_init(ks[2], (r, H, n + v), cfg.dtype, in_axis_size=r),
        "wo": dense_init(ks[3], (H, v, d), cfg.dtype, in_axis_size=H * v),
    }


def mla_fwd(cfg: ModelConfig, p: Params, x: jax.Array,
            positions: jax.Array, *, impl: str = "auto") -> jax.Array:
    """Causal self-attention of ``x`` (B, S, d) -> (B, S, d)."""
    H, r = cfg.num_heads, cfg.kv_lora_rank
    n, pr, v = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q = jnp.concatenate(
        [q[..., :n], apply_rope(q[..., n:], positions, cfg.rope_theta)], -1)
    kva = jnp.einsum("bsd,dk->bsk", x, p["wkva"])
    latent = rms_norm(kva[..., :r], p["kv_norm"]["scale"])
    k_rope = apply_rope(kva[..., None, r:], positions, cfg.rope_theta)
    kv = jnp.einsum("bsr,rhk->bshk", latent, p["wkvb"])
    k = jnp.concatenate(
        [kv[..., :n], jnp.broadcast_to(k_rope, kv.shape[:3] + (pr,))], -1)
    val = kv[..., n:]
    # the attention kernels take one head width for q, k and v: pad the
    # values to the score width and drop the padding from the output
    qk = n + pr
    if v < qk:
        val = jnp.pad(val, ((0, 0), (0, 0), (0, 0), (0, qk - v)))
    out = kops.attention(q, k, val, q_positions=positions,
                         kv_positions=positions, causal=True, impl=impl)
    return jnp.einsum("bshk,hkd->bsd", out[..., :v], p["wo"])
