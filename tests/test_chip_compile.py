"""Real-width compiles for a described TPU v5e chip (no chip attached).

The TPU compiler is installed alongside jax, so each test lowers a
kernel of the main path at its real width and compiles it for one chip
of a described ``v5e:2x2`` topology: the f64 frontier-grid sweep kernel,
the Pallas flash attention (forward and backward) at qwen1.5-4b width,
``wkv6`` at rwkv6-1.6b width and ``rglru`` at recurrentgemma-2b width.
A compile refuses what the chip's compiler would refuse — an op the
TPU's float64 emulation lacks, a block not aligned to the tiling, a
kernel over its fast memory — which interpret-mode tests cannot see.

The topology is described inside a module-scoped fixture, never while
a module is imported, and the fixture skips where it cannot be
described: only the worker that runs this file loads the TPU library.
The persistent compilation cache is off around these compiles (an
entry written for a described chip cannot be read back without one).
"""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(x, sharding):
    return jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype,
                                sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.memory_analysis() is not None
    return compiled


def test_frontier_sweep_kernel_f64(one_chip):
    """The fused two-tier kernel over the 51 840-scenario frontier grid,
    in float64 as the jax backend runs it (TPU f64 is emulated; this
    pins that every op of the kernel has an emulation)."""
    from repro.core.batched_jax import JaxGridEvaluator, _columns_jax
    from repro.core.scenarios import frontier_grid

    jev = JaxGridEvaluator(frontier_grid())
    with jax.enable_x64(True):
        args = [jax.tree_util.tree_map(lambda a: _spec(a, one_chip), t)
                for t in (jev._tables, jev._pflags, jev._kcodes,
                          jev._scodes, jev._ucodes)]
        compiled = _columns_jax.lower(
            *args, tl_overlaps=jev._tl_overlaps,
            coll_codes=jev._coll_codes).compile()
        out = compiled.out_info
    assert out["iteration_time_s"].shape == (len(jev),)
    assert out["iteration_time_s"].dtype == np.float64


def test_flash_attention_fwd_bwd_qwen15_4b(one_chip):
    from repro.kernels.flash_attention import flash_attention

    x = jax.ShapeDtypeStruct((2, 1024, 20, 128), jnp.bfloat16,
                             sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v).astype(jnp.float32))

    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2)), x, x, x)
    assert 'custom_call_target="tpu_custom_call"' in compiled.as_text()


def test_wkv6_rwkv6_16b(one_chip):
    from repro.configs import get_config
    from repro.kernels.wkv6 import wkv6

    cfg = get_config("rwkv6-1.6b")
    H, hd = cfg.num_heads, cfg.d_model // cfg.num_heads
    x = jax.ShapeDtypeStruct((2, 1024, H, hd), jnp.bfloat16,
                             sharding=one_chip)
    u = jax.ShapeDtypeStruct((H, hd), jnp.float32, sharding=one_chip)
    compiled = _compile(lambda r, k, v, w, u: wkv6(r, k, v, w, u),
                        x, x, x, x, u)
    assert 'custom_call_target="tpu_custom_call"' in compiled.as_text()


def test_rglru_recurrentgemma_2b(one_chip):
    from repro.configs import get_config
    from repro.kernels.rglru import rglru

    W = get_config("recurrentgemma-2b").rnn_size
    x = jax.ShapeDtypeStruct((2, 1024, W), jnp.bfloat16, sharding=one_chip)
    lam = jax.ShapeDtypeStruct((W,), jnp.float32, sharding=one_chip)
    compiled = _compile(lambda x, r, i, lam: rglru(x, r, i, lam),
                        x, x, x, lam)
    assert 'custom_call_target="tpu_custom_call"' in compiled.as_text()
