"""Analytic architecture cost model sanity checks."""
import pytest

from repro.configs import SHAPES, get_config
from repro.core.archcost import block_cost_table, param_counts, step_cost


def test_dense_model_flops_is_6nd():
    cfg = get_config("qwen1.5-4b")
    n, na = param_counts(cfg)
    assert n == na
    c = step_cost(cfg, SHAPES["train_4k"])
    D = 256 * 4096
    assert c.model_flops == pytest.approx(6 * na * D)
    assert c.flops > c.model_flops          # + attention terms


def test_moe_active_less_than_total():
    cfg = get_config("qwen2-moe-a2.7b")
    n, na = param_counts(cfg)
    assert na < 0.5 * n                     # top-4 of 60 + shared
    c = step_cost(cfg, SHAPES["train_4k"])
    assert c.model_flops == pytest.approx(6 * na * 256 * 4096)


def test_grok_scale():
    n, na = param_counts(get_config("grok-1-314b"))
    assert 250e9 < n < 340e9
    assert 70e9 < na < 100e9                # top-2 of 8 experts


def test_decode_flops_dominated_by_params():
    cfg = get_config("internlm2-20b")
    c = step_cost(cfg, SHAPES["decode_32k"])
    # one token/seq: 2*N*B plus attention over the 32k cache
    assert c.flops >= c.model_flops
    assert c.hbm_bytes > c.param_bytes      # params + kv cache traffic


def test_window_reduces_decode_cache():
    g = get_config("gemma3-1b")
    c = step_cost(g, SHAPES["long_500k"])
    # 22 local layers cache only 512 tokens; 4 global layers carry 524k
    full_equiv = 26 * 2 * 1 * 524_288 * 1 * 256 * 2
    assert c.hbm_bytes - c.param_bytes < full_equiv * 0.3


def test_ssm_long_decode_constant_state():
    cfg = get_config("rwkv6-1.6b")
    c500 = step_cost(cfg, SHAPES["long_500k"])
    # state is seq-length independent; hbm ~ params + small state
    assert c500.hbm_bytes < 1.2 * c500.param_bytes


def test_deepseek_v2_lite_counts():
    """Matrices only: 15 706 357 760 parameters, 2 661 023 744 active
    (6 of 64 routed experts, both shared ones, every MLA projection)."""
    cfg = get_config("deepseek-v2-lite")
    assert param_counts(cfg) == (15_706_357_760, 2_661_023_744)
    blocks = block_cost_table(cfg, 4096)
    assert len(blocks) == 1 + 27 + 1                 # embed, blocks, head
    mla = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048
    assert mla == 13_762_560
    assert blocks[1].params == mla + 3 * 2048 * 10944   # the dense layer
    assert blocks[1].routed_params == 0
    routed = 64 * 3 * 2048 * 1408
    assert all(b.routed_params == routed for b in blocks[2:-1])
    # causal MLA: S*S*H*(qk_nope + qk_rope + v) a sequence, forward
    S = 4096
    assert blocks[1].flops_fwd == 2.0 * blocks[1].active_params * S \
        + S * S * 16 * (128 + 64 + 128)
