"""Decoder-only LM over arbitrary ``layer_pattern`` block sequences.

Layers are executed as ``first_k_dense`` leading blocks with a dense
MLP (DeepSeek-V2's first layer), then ``num_units`` repetitions of the
pattern unit via ``lax.scan`` over stacked parameters (small HLO, fast
multi-pod compiles) plus an unstacked remainder — so gemma3's 5:1 local:global,
recurrentgemma's 2:1 recurrent:attention and llama-vision's 4:1
self:cross patterns all lower through the same code path.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.models import blocks as B
from repro.models.common import ModelConfig, Params, apply_norm, dense_init, \
    init_norm, split_keys
from repro.models.sharding import constrain


# ----------------------------------------------------------------------
# Init
# ----------------------------------------------------------------------
def init_unit(cfg: ModelConfig, key) -> Params:
    ks = split_keys(key, len(cfg.layer_pattern))
    return {f"b{i}": B.init_block(cfg, kind, ks[i])
            for i, kind in enumerate(cfg.layer_pattern)}


def init_lm(cfg: ModelConfig, key) -> Params:
    ks = split_keys(key, 4 + len(cfg.remainder_pattern))
    params: Params = {
        "embedding": dense_init(ks[0], (cfg.vocab_size, cfg.d_model),
                                cfg.dtype, in_axis_size=cfg.d_model),
        "final_norm": init_norm(cfg),
    }
    if cfg.first_k_dense:
        dense_keys = split_keys(ks[3], cfg.first_k_dense)
        for i in range(cfg.first_k_dense):
            params[f"dense{i}"] = B.init_block(cfg, cfg.layer_pattern[0],
                                               dense_keys[i], moe=False)
    if cfg.num_units > 0:
        unit_keys = jnp.stack(split_keys(ks[1], cfg.num_units))
        params["units"] = jax.vmap(lambda k: init_unit(cfg, k))(unit_keys)
    for i, kind in enumerate(cfg.remainder_pattern):
        params[f"rem{i}"] = B.init_block(cfg, kind, ks[4 + i])
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(ks[2], (cfg.d_model, cfg.vocab_size),
                                       cfg.dtype)
    return params


def param_count(params: Params) -> int:
    return sum(x.size for x in jax.tree_util.tree_leaves(params))


# ----------------------------------------------------------------------
# Forward (train / prefill)
# ----------------------------------------------------------------------
def forward(cfg: ModelConfig, params: Params, tokens: jax.Array, *,
            encoder_out: jax.Array | None = None,
            positions: jax.Array | None = None,
            remat: bool = False,
            param_hook=None) -> tuple[jax.Array, jax.Array]:
    """tokens: (B, S) int32 -> (logits (B, S, V) in logit_dtype, moe_aux).

    ``param_hook`` (see :func:`repro.comm.sync.wfbp_param_hook`) is
    applied to each scanned unit's parameters *inside* the scan body —
    its backward rule then runs per layer inside the backward loop,
    which is how WFBP's layer-wise gradient all-reduce is realized in
    HLO — and to the unscanned leaves at their use sites.
    """
    x, head, aux = _final_hidden(cfg, params, tokens,
                                 encoder_out=encoder_out,
                                 positions=positions, remat=remat,
                                 param_hook=param_hook)
    logits = jnp.einsum("bsd,dv->bsv", x, head).astype(cfg.logit_dtype)
    logits = constrain(logits, "batch", None, "tensor")
    return logits, aux


def _final_hidden(cfg: ModelConfig, params: Params, tokens: jax.Array, *,
                  encoder_out=None, positions=None, remat=False,
                  param_hook=None):
    ph = param_hook or (lambda p: p)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
    emb = ph(params["embedding"])
    x = emb[tokens]
    x = constrain(x, "batch", None, None)
    aux0 = jnp.zeros((), jnp.float32)
    for i in range(cfg.first_k_dense):
        x, a = B.apply_block(cfg, cfg.layer_pattern[0], ph(params[f"dense{i}"]),
                             x, positions, encoder_out)
        aux0 = aux0 + a

    def unit_body(carry, unit_params):
        x, aux = carry
        unit_params = ph(unit_params)
        for i, kind in enumerate(cfg.layer_pattern):
            x, a = B.apply_block(cfg, kind, unit_params[f"b{i}"], x,
                                 positions, encoder_out)
            aux = aux + a
        return (x, aux), None

    if remat:
        unit_body = jax.checkpoint(unit_body)

    if cfg.num_units > 0:
        (x, aux), _ = jax.lax.scan(unit_body, (x, aux0), params["units"])
    else:
        aux = aux0
    for i, kind in enumerate(cfg.remainder_pattern):
        x, a = B.apply_block(cfg, kind, ph(params[f"rem{i}"]), x, positions,
                             encoder_out)
        aux = aux + a

    x = apply_norm(cfg, ph(params["final_norm"]), x)
    head = emb.T if cfg.tie_embeddings else ph(params["lm_head"])
    return x, head, aux


# Vocab sizes at or above this use the chunked-xent path (the assigned
# archs have 51k-262k vocabularies; materializing (B,S,V) f32 logits
# fwd+bwd would dominate HBM).
CHUNKED_XENT_MIN_VOCAB = 16_384


def loss_fn(cfg: ModelConfig, params: Params, tokens: jax.Array,
            labels: jax.Array, *, encoder_out: jax.Array | None = None,
            aux_weight: float = 0.01, remat: bool = False,
            param_hook=None) -> tuple[jax.Array, dict]:
    x, head, aux = _final_hidden(cfg, params, tokens,
                                 encoder_out=encoder_out, remat=remat,
                                 param_hook=param_hook)
    if cfg.vocab_size >= CHUNKED_XENT_MIN_VOCAB:
        from repro.models.loss import chunked_cross_entropy
        loss = chunked_cross_entropy(x, head, labels)
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, head).astype(cfg.logit_dtype)
        logp = jax.nn.log_softmax(logits, axis=-1)
        loss = -jnp.mean(
            jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0])
    total = loss + aux_weight * aux
    return total, {"loss": loss, "moe_aux": aux}


# ----------------------------------------------------------------------
# Decode (serve_step)
# ----------------------------------------------------------------------
def init_cache(cfg: ModelConfig, batch: int, seq_len: int) -> Params:
    cache: Params = {}
    if cfg.num_units > 0:
        def one_unit(_):
            return {f"b{i}": B.init_block_cache(cfg, kind, batch, seq_len)
                    for i, kind in enumerate(cfg.layer_pattern)}
        cache["units"] = jax.vmap(one_unit)(jnp.arange(cfg.num_units))
    for i, kind in enumerate(cfg.remainder_pattern):
        cache[f"rem{i}"] = B.init_block_cache(cfg, kind, batch, seq_len)
    return cache


def decode_step(cfg: ModelConfig, params: Params, cache: Params,
                token: jax.Array, pos: jax.Array, *,
                encoder_out: jax.Array | None = None,
                seq_axis: str | None = None) -> tuple[jax.Array, Params]:
    """One-token decode.  token: (B,) int32; pos: scalar int32.
    Returns (logits (B, V), new_cache)."""
    x = params["embedding"][token][:, None, :]        # (B, 1, d)

    def unit_body(x, xs):
        unit_params, unit_cache = xs
        new_cache = {}
        for i, kind in enumerate(cfg.layer_pattern):
            x, nc = B.decode_block(cfg, kind, unit_params[f"b{i}"], x,
                                   unit_cache[f"b{i}"], pos,
                                   encoder_out=encoder_out,
                                   seq_axis=seq_axis)
            new_cache[f"b{i}"] = nc
        return x, new_cache

    new_cache: Params = {}
    if cfg.num_units > 0:
        x, new_cache["units"] = jax.lax.scan(
            unit_body, x, (params["units"], cache["units"]))
    for i, kind in enumerate(cfg.remainder_pattern):
        x, nc = B.decode_block(cfg, kind, params[f"rem{i}"], x,
                               cache[f"rem{i}"], pos,
                               encoder_out=encoder_out, seq_axis=seq_axis)
        new_cache[f"rem{i}"] = nc

    x = apply_norm(cfg, params["final_norm"], x)
    head = params["embedding"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", x, head).astype(cfg.logit_dtype)
    return logits[:, 0, :], new_cache


def prefill_via_decode(cfg: ModelConfig, params: Params, tokens: jax.Array,
                       seq_len: int, *, encoder_out=None) -> tuple[jax.Array, Params]:
    """Sequential prefill for the serving example (small models): feed
    tokens one at a time through ``decode_step``."""
    cache = init_cache(cfg, tokens.shape[0], seq_len)

    def step(carry, t):
        cache, pos = carry
        logits, cache = decode_step(cfg, params, cache, t, pos,
                                    encoder_out=encoder_out)
        return (cache, pos + 1), logits

    (cache, _), logits = jax.lax.scan(step, (cache, jnp.int32(0)), tokens.T)
    return jnp.moveaxis(logits, 0, 1), cache
