"""Pallas TPU flash attention (causal / sliding-window, GQA).

Blockwise softmax attention with explicit BlockSpec VMEM tiling:
grid = (batch, q_heads, q_blocks, kv_blocks), the kv dimension
innermost/sequential, with running max / sum / accumulator scratch in
VMEM (the standard online-softmax flash schedule).  GQA is handled in
the k/v index maps (``h -> h // group``), so KV blocks are fetched
once per group position without materializing expanded heads in HBM.

Causal + window block skipping: fully-masked kv blocks are skipped at
grid level (``@pl.when``), which for sliding-window layers (gemma3 'L'
blocks) makes the kernel O(S * window) instead of O(S^2) — the TPU
adaptation of the sub-quadratic requirement for the long-context
shapes.

Differentiable: the kernel also writes each row's logsumexp, and a
``custom_vjp`` hands ``(q, k, v, out, lse)`` to the recomputing
backward of :mod:`repro.kernels.chunked_attention` — the same
residuals that module's own forward saves — so training steps take
the Pallas forward without a second backward implementation.

Validated against :func:`repro.kernels.ref.attention` in interpret
mode (CPU) over shape/dtype sweeps, gradients included;
``ops.attention`` routes here on TPU backends.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import chunked_attention

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
                  block_q: int, block_k: int, num_kv_blocks: int,
                  causal: bool, window: int | None, scale: float):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * block_q
    k_start = ik * block_k
    # Block-level skip: entirely above the causal diagonal, or entirely
    # left of the sliding window.
    run = jnp.bool_(True)
    if causal:
        run &= k_start <= q_start + block_q - 1
    if window is not None:
        run &= (k_start + block_k - 1) >= (q_start - window + 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)                  # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)                  # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                                  # (bq,)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1)
        acc_scr[...] = (acc_scr[...] * corr[:, None]
                        + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ()))))
        m_scr[...] = m_new

    @pl.when(ik == num_kv_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_scr[...] + jnp.log(l))[None, :]


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool = False) -> jax.Array:
    """q: (B, S, H, hd); k, v: (B, S, K, hd) with H % K == 0.
    Self-attention (q and kv positions aligned).  Returns (B, S, H, hd).
    ``interpret=True`` runs the kernel body in Python (tests off-TPU).
    """
    S, H, K = q.shape[1], q.shape[2], k.shape[2]
    if H % K:
        raise ValueError(f"H={H} not a multiple of K={K}")
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    if S % block_q or S % block_k:
        raise ValueError(f"S={S} must divide block sizes {block_q}/{block_k}")
    return _flash(q, k, v, causal, window, block_q, block_k, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, window, block_q, block_k, interpret):
    return _flash_call(q, k, v, causal, window, block_q, block_k,
                       interpret)[0]


def _flash_vjp_fwd(q, k, v, causal, window, block_q, block_k, interpret):
    out, lse = _flash_call(q, k, v, causal, window, block_q, block_k,
                           interpret)
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(causal, window, block_q, block_k, interpret, res, dout):
    return chunked_attention._vjp_bwd(causal, window, block_q, block_k,
                                      res, dout)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _flash_call(q, k, v, causal, window, block_q, block_k, interpret):
    """The Pallas forward: ``(out (B, S, H, hd), lse (B, S, H) f32)``."""
    B, S, H, hd = q.shape
    G = H // k.shape[2]
    nq, nk = S // block_q, S // block_k
    scale = 1.0 / math.sqrt(hd)

    qT = jnp.moveaxis(q, 2, 1)      # (B, H, S, hd)
    kT = jnp.moveaxis(k, 2, 1)      # (B, K, S, hd)
    vT = jnp.moveaxis(v, 2, 1)

    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, num_kv_blocks=nk,
        causal=causal, window=window, scale=scale)

    # lse rows are (1, S) slabs: a (1, block_q) block is tile-aligned
    # (full second-minor dim, 128-multiple minor dim)
    out, lse = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, iq, ik: (b, h // G, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, 1, block_q),
                         lambda b, h, iq, ik: (b, h, 0, iq)),
        ],
        out_shape=[jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
                   jax.ShapeDtypeStruct((B, H, 1, S), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qT, kT, vT)
    return jnp.moveaxis(out, 1, 2), jnp.moveaxis(lse[:, :, 0], 1, 2)
