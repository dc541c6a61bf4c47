"""Pallas TPU kernel for the RWKV6 (Finch) wkv scan with
data-dependent decay.

Recurrence (per head, state S in R^{hd x hd}):

    S_t = diag(w_t) S_{t-1} + k_t v_t^T
    o_t = r_t (S_{t-1} + diag(u) k_t v_t^T)

A naive port would loop token-by-token — hostile to the MXU.  The TPU
adaptation reformulates each time *block* in log-decay space so the
intra-block part becomes two matmuls (the chunked linear-attention
trick):

    L_t   = sum_{j<=t} log w_j              (per channel, within block)
    r'_t  = r_t * exp(L_{t-1}),   k'_i = k_i * exp(-L_i)
    intra = tril_strict(r' k'^T) V  + diag-bonus (u term)
    cross = r' @ S_prev
    S_new = exp(L_last) * S_prev + (k * exp(L_last - L))^T V

Grid = (batch, heads, time_blocks) with the time dimension sequential
and the running state in VMEM scratch; the carried initial state makes
the same kernel serve chunked prefill and decode.  Block size is kept
small (64) so exp(-L) stays in fp32 range — strongly-decayed channels
underflow to zero exactly as they vanish mathematically.

Oracle: :func:`repro.kernels.ref.wkv6`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_T = 64


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref,
                 o_ref, sout_ref, state_scr, *,
                 block_t: int, num_t_blocks: int):
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        state_scr[...] = s0_ref[0, 0].astype(jnp.float32)

    r = r_ref[0, 0].astype(jnp.float32)          # (T, hd)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    w = w_ref[0, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)             # (1, hd)
    S = state_scr[...]                           # (hd, hd)

    logw = jnp.log(jnp.maximum(w, 1e-38))
    ti = jax.lax.broadcasted_iota(jnp.int32, (block_t, block_t), 0)
    tj = jax.lax.broadcasted_iota(jnp.int32, (block_t, block_t), 1)
    # L_t = sum_{j<=t} log w_j, as a lower-triangular matmul (a TPU
    # kernel has no cumsum); full f32 precision, it feeds exp()
    L = jax.lax.dot_general((tj <= ti).astype(jnp.float32), logw,
                            (((1,), (0,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST)  # (T, hd)
    L_prev = L - logw                            # L_{t-1}
    r_scaled = r * jnp.exp(L_prev)
    k_scaled = k * jnp.exp(-L)

    # intra-block strict-lower attention + diagonal u-bonus
    scores = jax.lax.dot_general(r_scaled, k_scaled,
                                 (((1,), (1,)), ((), ())))      # (T, T)
    scores = jnp.where(tj < ti, scores, 0.0)
    diag = jnp.sum(r * u * k, axis=-1, keepdims=True)           # (T, 1)
    o = (jax.lax.dot_general(scores, v, (((1,), (0,)), ((), ())))
         + diag * v
         + jax.lax.dot_general(r * jnp.exp(L_prev), S,
                               (((1,), (0,)), ((), ()))))
    o_ref[0, 0] = o.astype(o_ref.dtype)

    # state to next block: the block's total log-decay as a row (static
    # slice) and as a column (transposed matmul; no vector relayout)
    L_last = L[block_t - 1:, :]                                 # (1, hd)
    L_last_col = jax.lax.dot_general(
        logw, jnp.ones((block_t, 1), jnp.float32), (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST)                    # (hd, 1)
    k_tail = k * jnp.exp(L_last - L)                            # (T, hd)
    S_new = (jnp.exp(L_last_col) * S
             + jax.lax.dot_general(k_tail, v, (((0,), (0,)), ((), ()))))
    state_scr[...] = S_new

    @pl.when(it == num_t_blocks - 1)
    def _final():
        sout_ref[0, 0] = S_new.astype(sout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def wkv6(r, k, v, w, u, state=None, *, block_t: int = DEFAULT_BLOCK_T,
         interpret: bool = False):
    """r,k,v,w: (B, S, H, hd); u: (H, hd); state: (B, H, hd, hd) f32.
    Returns (out (B,S,H,hd), final_state (B,H,hd,hd))."""
    B, S, H, hd = r.shape
    block_t = min(block_t, S)
    if S % block_t:
        raise ValueError(f"S={S} not a multiple of block_t={block_t}")
    nt = S // block_t
    if state is None:
        state = jnp.zeros((B, H, hd, hd), jnp.float32)

    def tr(x):
        return jnp.moveaxis(x, 2, 1)             # (B, H, S, hd)

    kernel = functools.partial(_wkv6_kernel, block_t=block_t, num_t_blocks=nt)
    out, sout = pl.pallas_call(
        kernel,
        grid=(B, H, nt),
        in_specs=[
            pl.BlockSpec((1, 1, block_t, hd), lambda b, h, it: (b, h, it, 0)),
            pl.BlockSpec((1, 1, block_t, hd), lambda b, h, it: (b, h, it, 0)),
            pl.BlockSpec((1, 1, block_t, hd), lambda b, h, it: (b, h, it, 0)),
            pl.BlockSpec((1, 1, block_t, hd), lambda b, h, it: (b, h, it, 0)),
            # u as (H, 1, hd): a (1, hd) slab is tile-aligned, a row
            # of an (H, hd) array is not
            pl.BlockSpec((1, 1, hd), lambda b, h, it: (h, 0, 0)),
            pl.BlockSpec((1, 1, hd, hd), lambda b, h, it: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_t, hd), lambda b, h, it: (b, h, it, 0)),
            pl.BlockSpec((1, 1, hd, hd), lambda b, h, it: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, hd), r.dtype),
            jax.ShapeDtypeStruct((B, H, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=interpret,
    )(tr(r), tr(k), tr(v), tr(w), u.reshape(H, 1, hd), state)
    return jnp.moveaxis(out, 1, 2), sout
