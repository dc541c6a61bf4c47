"""Pallas TPU kernel for the RG-LRU gated linear recurrence
(RecurrentGemma / Griffin):

    log a_t = -c * softplus(Lambda) * sigmoid(r_t)
    h_t     = a_t h_{t-1} + sqrt(1 - a_t^2) * (sigmoid(i_t) * x_t)

The recurrence is elementwise over the width dimension (VPU work), so
the TPU schedule tiles (time_block, width_block) into VMEM, runs the
time recurrence as an in-register ``fori_loop`` over rows, and carries
``h`` across sequential time blocks in scratch.  Width blocks ride a
parallel grid dimension (lane-aligned, 128 multiple).

Oracle: :func:`repro.kernels.ref.rglru`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import RGLRU_C

DEFAULT_BLOCK_T = 128
DEFAULT_BLOCK_W = 128


def _rglru_kernel(x_ref, r_ref, i_ref, lam_ref, h0_ref, o_ref, hout_ref,
                  h_scr, a_scr, g_scr, out_scr, *, block_t: int,
                  num_t_blocks: int):
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(jnp.float32)

    x = x_ref[0].astype(jnp.float32)             # (T, W)
    r = r_ref[0].astype(jnp.float32)
    i = i_ref[0].astype(jnp.float32)
    lam = lam_ref[...].astype(jnp.float32)       # (1, W)

    log_a_base = -RGLRU_C * jax.nn.softplus(lam)     # (1, W)
    log_a = log_a_base * jax.nn.sigmoid(r)           # (T, W)
    a_scr[...] = jnp.exp(log_a)
    mult = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12))
    g_scr[...] = jax.nn.sigmoid(i) * x * mult        # (T, W)

    # the sequential recurrence walks the block's rows through VMEM
    # refs (a TPU kernel indexes refs, not values, at a traced step)
    def body(t, h):
        row = pl.ds(t, 1)
        h = a_scr[row, :] * h + g_scr[row, :]        # (1, W)
        out_scr[row, :] = h
        return h

    h = jax.lax.fori_loop(0, block_t, body, h_scr[...])
    o_ref[0] = out_scr[...].astype(o_ref.dtype)
    h_scr[...] = h

    @pl.when(it == num_t_blocks - 1)
    def _final():
        hout_ref[0] = h.astype(hout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_t", "block_w", "interpret"))
def rglru(x, r_gate, i_gate, lam, h0=None, *,
          block_t: int = DEFAULT_BLOCK_T, block_w: int = DEFAULT_BLOCK_W,
          interpret: bool = False):
    """x, r_gate, i_gate: (B, S, W); lam: (W,); h0: (B, W) f32.
    Returns (out (B,S,W), h_final (B,W))."""
    B, S, W = x.shape
    block_t = min(block_t, S)
    block_w = min(block_w, W)
    if S % block_t or W % block_w:
        raise ValueError(f"S={S}/W={W} not multiples of blocks "
                         f"{block_t}/{block_w}")
    nt, nw = S // block_t, W // block_w
    if h0 is None:
        h0 = jnp.zeros((B, W), jnp.float32)
    lam2 = lam.reshape(1, W)

    kernel = functools.partial(_rglru_kernel, block_t=block_t,
                               num_t_blocks=nt)
    out, hout = pl.pallas_call(
        kernel,
        grid=(B, nw, nt),
        in_specs=[
            pl.BlockSpec((1, block_t, block_w), lambda b, iw, it: (b, it, iw)),
            pl.BlockSpec((1, block_t, block_w), lambda b, iw, it: (b, it, iw)),
            pl.BlockSpec((1, block_t, block_w), lambda b, iw, it: (b, it, iw)),
            pl.BlockSpec((1, block_w), lambda b, iw, it: (0, iw)),
            # per-row state as (B, 1, W): a (1, block_w) slab is
            # tile-aligned, a row of a (B, W) array is not
            pl.BlockSpec((1, 1, block_w), lambda b, iw, it: (b, 0, iw)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_t, block_w), lambda b, iw, it: (b, it, iw)),
            pl.BlockSpec((1, 1, block_w), lambda b, iw, it: (b, 0, iw)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, W), x.dtype),
            jax.ShapeDtypeStruct((B, 1, W), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, block_w), jnp.float32)]
        + [pltpu.VMEM((block_t, block_w), jnp.float32)] * 3,
        interpret=interpret,
    )(x, r_gate, i_gate, lam2, h0.reshape(B, 1, W))
    return out, hout.reshape(B, W)
