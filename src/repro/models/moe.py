"""Mixture-of-Experts MLP with grouped einsum dispatch (Mesh-TF /
MaxText style — SPMD-friendly, expert-parallel over the ``model`` mesh
axis).

Tokens are processed in groups; each group computes a top-k router,
builds a (group, expert, capacity) dispatch/combine pair, and the
expert FFNs run as a single batched einsum over the expert dimension.
Dropped tokens (over capacity) fall through the residual connection,
the standard capacity-factor behaviour.  Shared experts (qwen2-moe)
are a plain dense MLP fused to ``shared_expert_d_ff``.  The top-k gates
are the router's softmax probabilities, renormalized to sum to one
unless ``norm_topk_prob`` is off (DeepSeek-V2 keeps them as they are).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import ModelConfig, Params, dense_init, split_keys


def init_moe(cfg: ModelConfig, key) -> Params:
    d, E, ff = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    ks = split_keys(key, 7)
    p: Params = {
        "router": dense_init(ks[0], (d, E), jnp.float32, in_axis_size=d),
        "wi": dense_init(ks[1], (E, d, ff), cfg.dtype, in_axis_size=d),
        "wg": dense_init(ks[2], (E, d, ff), cfg.dtype, in_axis_size=d),
        "wo": dense_init(ks[3], (E, ff, d), cfg.dtype, in_axis_size=ff),
    }
    if cfg.shared_expert_d_ff:
        sf = cfg.shared_expert_d_ff
        p["shared"] = {
            "wi": dense_init(ks[4], (d, sf), cfg.dtype, in_axis_size=d),
            "wg": dense_init(ks[5], (d, sf), cfg.dtype, in_axis_size=d),
            "wo": dense_init(ks[6], (sf, d), cfg.dtype, in_axis_size=sf),
        }
    return p


def _capacity(cfg: ModelConfig, group: int) -> int:
    c = int(group * cfg.experts_per_token * cfg.capacity_factor
            / max(cfg.num_experts, 1))
    return max(c, 1)


def moe_mlp(cfg: ModelConfig, p: Params, x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (out, aux_loss).

    aux_loss is the standard load-balancing loss (mean over groups of
    E * sum_e fraction_e * router_prob_e), returned for the trainer.
    """
    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    tokens = x.reshape(-1, d)
    T = tokens.shape[0]
    g = min(cfg.moe_group_size, T)
    # pad to a multiple of the group size
    pad = (-T) % g
    if pad:
        tokens = jnp.concatenate([tokens, jnp.zeros((pad, d), tokens.dtype)])
    G = tokens.shape[0] // g
    xg = tokens.reshape(G, g, d)
    C = _capacity(cfg, g)

    logits = jnp.einsum("Ggd,dE->GgE", xg.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)                       # (G,g,E)
    gate_vals, top_e = jax.lax.top_k(probs, k)                    # (G,g,k)
    if cfg.norm_topk_prob:
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    # position of each (token, choice) within its expert's capacity
    onehot = jax.nn.one_hot(top_e, E, dtype=jnp.int32)            # (G,g,k,E)
    flat = onehot.reshape(G, g * k, E)
    pos_in_e = jnp.cumsum(flat, axis=1) - flat                    # (G,g*k,E)
    pos = jnp.sum(pos_in_e * flat, axis=-1).reshape(G, g, k)      # (G,g,k)
    keep = pos < C

    # dispatch/combine tensors (G, g, E, C)
    pos_oh = jax.nn.one_hot(jnp.where(keep, pos, C), C, dtype=xg.dtype)
    disp = jnp.einsum("GgkE,Ggkc->GgEc",
                      onehot.astype(xg.dtype) * keep[..., None], pos_oh)
    comb = jnp.einsum("Ggk,GgkE,Ggkc->GgEc",
                      gate_vals.astype(xg.dtype),
                      onehot.astype(xg.dtype) * keep[..., None], pos_oh)

    expert_in = jnp.einsum("GgEc,Ggd->EGcd", disp, xg)            # (E,G,C,d)
    h = jnp.einsum("EGcd,Edf->EGcf", expert_in, p["wi"])
    gates = jnp.einsum("EGcd,Edf->EGcf", expert_in, p["wg"])
    h = h * jax.nn.silu(gates.astype(jnp.float32)).astype(h.dtype)
    expert_out = jnp.einsum("EGcf,Efd->EGcd", h, p["wo"])
    out = jnp.einsum("GgEc,EGcd->Ggd", comb, expert_out)

    out = out.reshape(-1, d)[:T].reshape(B, S, d)

    # load-balance auxiliary loss (Switch-style)
    frac = jnp.mean(jnp.sum(onehot[..., 0, :] if k == 1 else
                            jnp.max(onehot, axis=2), axis=1) / g, axis=0)
    mean_prob = jnp.mean(probs, axis=(0, 1))
    aux = E * jnp.sum(frac * mean_prob)

    if "shared" in p:
        sp = p["shared"]
        hs = jnp.einsum("bsd,df->bsf", x, sp["wi"])
        gs = jnp.einsum("bsd,df->bsf", x, sp["wg"])
        hs = hs * jax.nn.silu(gs.astype(jnp.float32)).astype(hs.dtype)
        out = out + jnp.einsum("bsf,fd->bsd", hs, sp["wo"])
    return out, aux
