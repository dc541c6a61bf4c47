"""Jit-ready kernel entry points with implementation dispatch.

``impl``:
  * ``"ref"``     — pure-jnp oracle (:mod:`repro.kernels.ref`)
  * ``"pallas"``  — Pallas TPU kernel; raises ``ValueError`` on shapes
    the kernel does not cover (never a silent substitute)
  * ``"auto"``    — pallas on TPU backends where the shapes fit, the jnp
    paths elsewhere (CPU dry-runs lower the jnp path; the TPU
    deployment takes the kernel path)

The kernels' own ``interpret=True`` (Python execution of a kernel
body off-TPU) is for tests that call them directly; dispatch here
always compiles the kernel for the chip.
"""
from __future__ import annotations

import jax

from repro.kernels import ref


def _resolve(impl: str) -> str:
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return impl


def _uncovered(kernel: str, why: str):
    return ValueError(f"impl='pallas': the {kernel} kernel does not cover "
                      f"{why}; use impl='auto' or impl='ref'")


# Self-attention sequences at or above this length route to the
# chunked (flash-schedule) implementation: the naive path materializes
# (B, H, S, S) scores, which at 32k+ dominates device memory.
CHUNKED_ATTENTION_MIN_SEQ = 2048


def attention(q, k, v, *, q_positions=None, kv_positions=None, causal=True,
              window=None, impl: str = "auto"):
    S = q.shape[1]
    aligned_self = q.shape[1] == k.shape[1] and causal
    if _resolve(impl) == "pallas":
        from repro.kernels import flash_attention as fa
        # The Pallas kernel covers self-attention with equal q/kv lengths
        # and row-aligned positions.
        if (aligned_self and S % fa.DEFAULT_BLOCK_Q == 0
                and q.shape[-1] % 128 == 0):
            return fa.flash_attention(q, k, v, causal=causal, window=window)
        if impl == "pallas":
            raise _uncovered(
                "flash attention",
                f"q {q.shape} / kv {k.shape} (needs causal self-attention, "
                f"S % {fa.DEFAULT_BLOCK_Q} == 0, head_dim % 128 == 0)")
    if (impl in ("auto", "chunked") and aligned_self
            and S >= CHUNKED_ATTENTION_MIN_SEQ):
        from repro.kernels import chunked_attention as ca
        block = 512 if S % 512 == 0 else next(
            b for b in (256, 128, 64, 1) if S % b == 0)
        return ca.chunked_attention(q, k, v, causal, window, block, block)
    return ref.attention(q, k, v, q_positions=q_positions,
                         kv_positions=kv_positions, causal=causal,
                         window=window)


def decode_attention(q, k, v, valid, impl: str = "auto"):
    return ref.decode_attention(q, k, v, valid)


def decode_attention_partials(q, k, v, valid, impl: str = "auto"):
    return ref.decode_attention_partials(q, k, v, valid)


def wkv6(r, k, v, w, u, state=None, impl: str = "auto"):
    if _resolve(impl) == "pallas":
        from repro.kernels import wkv6 as wk
        if r.shape[1] % wk.DEFAULT_BLOCK_T == 0:
            return wk.wkv6(r, k, v, w, u, state=state)
        if impl == "pallas":
            raise _uncovered("wkv6", f"sequence length {r.shape[1]} "
                             f"(needs a multiple of {wk.DEFAULT_BLOCK_T})")
    return ref.wkv6(r, k, v, w, u, state=state)


def rglru(x, r_gate, i_gate, lam, h0=None, impl: str = "auto"):
    if _resolve(impl) == "pallas":
        from repro.kernels import rglru as rg
        if x.shape[1] % rg.DEFAULT_BLOCK_T == 0 and x.shape[2] % 128 == 0:
            return rg.rglru(x, r_gate, i_gate, lam, h0=h0)
        if impl == "pallas":
            raise _uncovered("rglru", f"x {x.shape} (needs S % "
                             f"{rg.DEFAULT_BLOCK_T} == 0, width % 128 == 0)")
    return ref.rglru(x, r_gate, i_gate, lam, h0=h0)
