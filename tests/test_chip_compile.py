"""Real-width compiles for a described TPU v5e chip (no chip attached).

The TPU compiler is installed alongside jax, so each test lowers a
kernel of the main path at its real width and compiles it for one chip
of a described ``v5e:2x2`` topology: the f64 sweep kernel on the
frontier grid and, with its expert-parallel terms, on DeepSeek-V2-Lite's,
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
    pins that every op of the kernel has an emulation).  Its one output
    is the packed buffer the host fetches in one transfer: the two
    float32 words of each of the six emulated float64 columns, each
    padded to whole tiles of 1 024."""
    from repro.core.batched_jax import _NUMERIC_COLS, JaxGridEvaluator
    from repro.core.scenarios import frontier_grid

    compiled = _sweep_kernel(JaxGridEvaluator(frontier_grid()), one_chip)
    out = compiled.out_info
    assert isinstance(out, jax.ShapeDtypeStruct)
    assert out.shape == (1, 2 * len(_NUMERIC_COLS) * 52_224)   # 51 tiles
    assert out.dtype == np.float32
    assert "f64[" in compiled.as_text()


def _sweep_kernel(jev, sharding, *, codes=None, shards=1):
    """The jitted sweep kernel of ``jev`` compiled for ``sharding``, its
    outputs packed as on one TPU (``shards`` 1).  Its inputs are packed
    as the host packs them: every leaf into the float64 or the int32
    buffer, placed with ``sharding``, except the codes given a sharding
    in ``codes``, which pass as device arrays of that sharding, as a
    mesh's do.  The module keeps its name, ``jit__columns_jax``, which
    the benchmark's device readers look for."""
    import re

    from repro.core.batched_jax import _columns_jax, _packed_args

    tables, pflags, kcodes, scodes, ucodes, *static = jev._args()
    if codes is not None:
        kcodes, scodes = ({k: _spec(v, codes) for k, v in d.items()}
                          for d in (kcodes, scodes))
    with jax.enable_x64(True):
        f64, i32, live, layout, tl_overlaps, coll_codes = _packed_args(
            (tables, pflags, kcodes, scodes, ucodes, *static))
        lowered = _columns_jax.lower(
            _spec(f64, sharding), _spec(i32, sharding), live, layout,
            tl_overlaps, coll_codes, shards=shards)
        assert re.search(r"module @jit__columns_jax\b", lowered.as_text())
        return lowered.compile()


def test_dsv2_lite_ep_sweep_kernel_f64(one_chip):
    """The kernel with its expert-parallel terms, over the 80 640
    scenarios of DeepSeek-V2-Lite under data x expert parallelism (EP
    sizes 1-64 on 64-512 workers): the all-to-all suffix tables, the
    expert-group coefficients and the per-pair bucket tables all have
    an emulation in float64."""
    from repro.core.batched_jax import _NUMERIC_COLS, JaxGridEvaluator
    from repro.core.hardware import COLLECTIVE_ALGORITHMS
    from repro.core.scenarios import FRONTIER_POLICIES, ScenarioGrid

    links = tuple(f"{base}@bw{bw:g}@lat{lat:g}"
                  for base in ("10gbe", "ib-100g", "ib-100g-fused", "ib-200g")
                  for bw in (0.5, 1, 2, 4) for lat in (0.25, 1, 4))
    grid = ScenarioGrid(workloads=("llm:deepseek-v2-lite",),
                        clusters=("v100-nvlink-ib", "tpu-v5e-pod"),
                        worker_counts=(64, 128, 256, 512),
                        ep_sizes=(1, 2, 4, 8, 16, 32, 64),
                        policies=FRONTIER_POLICIES,
                        collectives=COLLECTIVE_ALGORITHMS, interconnects=links)
    jev = JaxGridEvaluator(grid)
    assert "we" in jev._kcodes
    compiled = _sweep_kernel(jev, one_chip)
    out = compiled.out_info
    assert out.shape == (1, 2 * len(_NUMERIC_COLS) * 80_896)   # 79 tiles
    assert out.dtype == np.float32
    mem = compiled.memory_analysis()
    print(f"dsv2_lite_ep kernel: args {mem.argument_size_in_bytes} B, "
          f"outputs {mem.output_size_in_bytes} B, temps "
          f"{mem.temp_size_in_bytes} B")


def test_frontier_sweep_kernel_packs_on_four_chips_in_place(topo):
    """On a 2x2 mesh, the scenario axis sharded as ``JaxGridEvaluator``
    shards it — the codes split over ``data`` as device arrays, the two
    packed host buffers replicated — each chip packs the words of its
    own block: no all-gather, all-to-all or collective-permute moves the
    packed buffer."""
    import re

    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from repro.core.batched_jax import _NUMERIC_COLS, JaxGridEvaluator
    from repro.core.scenarios import frontier_grid

    mesh = Mesh(np.array(topo.devices).reshape(4), ("data",))
    rep = NamedSharding(mesh, PartitionSpec())
    split = NamedSharding(mesh, PartitionSpec("data"))
    compiled = _sweep_kernel(JaxGridEvaluator(frontier_grid()), rep,
                             codes=split, shards=4)
    out = compiled.out_info
    assert out.shape == (4, 2 * len(_NUMERIC_COLS) * 13_312)  # 13 tiles
    assert out.sharding.spec == PartitionSpec("data")
    text = compiled.as_text()
    for op in ("all-gather", "all-to-all", "collective-permute"):
        assert not re.search(op + r"(-start)?\(", text), op


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
