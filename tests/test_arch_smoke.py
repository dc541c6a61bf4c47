"""Per-architecture smoke tests (deliverable f): a REDUCED variant of
each assigned config (2 layers, d_model<=512, <=4 experts) runs one
forward and one train step on CPU with shape + finiteness asserts."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models import encdec as ED
from repro.models import transformer as T
from repro.optim.sgd import sgd

B, S = 2, 16


def _inputs(cfg, key):
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    labels = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    extra = {}
    if cfg.arch_type == "audio":
        extra["frames"] = jax.random.normal(
            key, (B, cfg.encoder_seq, cfg.d_model), cfg.dtype)
    if cfg.arch_type == "vlm":
        extra["images"] = jax.random.normal(
            key, (B, cfg.num_image_tokens, cfg.d_model), cfg.dtype)
    return tokens, labels, extra


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_shapes_and_finiteness(arch):
    cfg = get_config(arch).reduced()
    assert cfg.d_model <= 512 and cfg.num_layers <= 2
    assert cfg.num_experts <= 4
    key = jax.random.PRNGKey(0)
    tokens, _, extra = _inputs(cfg, key)
    if cfg.arch_type == "audio":
        params = ED.init_encdec(cfg, key)
        logits, aux = ED.forward(cfg, params, extra["frames"], tokens)
    else:
        params = T.init_lm(cfg, key)
        logits, aux = T.forward(cfg, params, tokens,
                                encoder_out=extra.get("images"))
    assert logits.shape == (B, S, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_one_train_step(arch):
    cfg = get_config(arch).reduced()
    key = jax.random.PRNGKey(1)
    tokens, labels, extra = _inputs(cfg, key)
    opt = sgd(lr=1e-2, momentum=0.9)
    if cfg.arch_type == "audio":
        params = ED.init_encdec(cfg, key)
        loss = lambda p: ED.loss_fn(cfg, p, extra["frames"], tokens, labels)[0]
    else:
        params = T.init_lm(cfg, key)
        loss = lambda p: T.loss_fn(cfg, p, tokens, labels,
                                   encoder_out=extra.get("images"))[0]
    l0, grads = jax.value_and_grad(loss)(params)
    assert bool(jnp.isfinite(l0))
    gleaves = jax.tree_util.tree_leaves(grads)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in gleaves)
    state = opt.init(params)
    new_params, _ = opt.update(grads, state, params)
    l1 = loss(new_params)
    assert bool(jnp.isfinite(l1))
    # a gradient step on the same batch should not increase loss much
    assert float(l1) < float(l0) + 0.5


@pytest.mark.parametrize("arch", ["gemma3-1b", "rwkv6-1.6b",
                                  "recurrentgemma-2b", "qwen2-moe-a2.7b",
                                  "llama-3.2-vision-90b", "whisper-tiny"])
def test_decode_matches_forward(arch):
    cfg = get_config(arch).reduced()
    key = jax.random.PRNGKey(2)
    tokens, _, extra = _inputs(cfg, key)
    if cfg.arch_type == "audio":
        params = ED.init_encdec(cfg, key)
        enc = ED.encode(cfg, params["encoder"], extra["frames"])
        fwd, _ = T.forward(cfg, params["decoder"], tokens, encoder_out=enc)
        dec, _ = T.prefill_via_decode(cfg, params["decoder"], tokens, S,
                                      encoder_out=enc)
    else:
        params = T.init_lm(cfg, key)
        enc = extra.get("images")
        fwd, _ = T.forward(cfg, params, tokens, encoder_out=enc)
        dec, _ = T.prefill_via_decode(cfg, params, tokens, S, encoder_out=enc)
    scale = float(jnp.max(jnp.abs(fwd))) + 1e-6
    assert float(jnp.max(jnp.abs(fwd - dec))) / scale < 5e-4


def test_full_configs_match_assignment():
    """The full (non-reduced) configs carry the exact assigned dims."""
    expect = {
        "whisper-tiny": (4, 384, 6, 6, 1536, 51865),
        "internlm2-20b": (48, 6144, 48, 8, 16384, 92544),
        "qwen2-moe-a2.7b": (24, 2048, 16, 16, 1408, 151936),
        "qwen1.5-4b": (40, 2560, 20, 20, 6912, 151936),
        "gemma3-1b": (26, 1152, 4, 1, 6912, 262144),
        "grok-1-314b": (64, 6144, 48, 8, 32768, 131072),
        "rwkv6-1.6b": (24, 2048, 32, 32, 7168, 65536),
        "recurrentgemma-2b": (26, 2560, 10, 1, 7680, 256000),
        "llama-3.2-vision-90b": (100, 8192, 64, 8, 28672, 128256),
        "qwen1.5-32b": (64, 5120, 40, 40, 27392, 152064),
        "deepseek-v2-lite": (27, 2048, 16, 16, 10944, 102400),
    }
    for arch, (L, d, H, K, ff, V) in expect.items():
        cfg = get_config(arch)
        assert cfg.num_layers == L and cfg.d_model == d, arch
        assert cfg.num_heads == H and cfg.kv_heads == K, arch
        assert cfg.d_ff == ff and cfg.vocab_size == V, arch
        assert cfg.source, f"{arch} missing citation"


def test_moe_counts():
    moe = get_config("qwen2-moe-a2.7b")
    assert moe.num_experts == 60 and moe.experts_per_token == 4
    assert moe.shared_expert_d_ff == 4 * 1408
    grok = get_config("grok-1-314b")
    assert grok.num_experts == 8 and grok.experts_per_token == 2
    ds = get_config("deepseek-v2-lite")
    assert (ds.num_experts, ds.experts_per_token, ds.moe_d_ff) == (64, 6, 1408)
    assert ds.shared_expert_d_ff == 2 * 1408 and ds.first_k_dense == 1
    assert not ds.norm_topk_prob
    assert (ds.kv_lora_rank, ds.qk_nope_head_dim, ds.qk_rope_head_dim,
            ds.v_head_dim) == (512, 128, 64, 128)


def _matrix_params(params) -> int:
    """Parameters of a model pytree, norm scales excepted (the cost
    model counts matrices only)."""
    return sum(x.size for path, x in
               jax.tree_util.tree_leaves_with_path(params)
               if not any("norm" in str(k) for k in path))


@pytest.mark.parametrize("over", [{}, {"num_layers": 4, "num_experts": 8}])
def test_deepseek_param_count_matches_cost_model(over):
    """The reduced model's real parameters are what
    :func:`repro.core.archcost.param_counts` counts: MLA's four
    projections, the leading dense layer, routed and shared experts,
    the router, embedding and head."""
    from repro.core.archcost import param_counts
    cfg = get_config("deepseek-v2-lite").reduced(**over)
    assert cfg.first_k_dense == 1 and cfg.is_mla
    params = T.init_lm(cfg, jax.random.PRNGKey(3))
    assert "dense0" in params and "moe" in params["units"]["b0"]
    assert _matrix_params(params) == param_counts(cfg)[0]


def test_latent_attention_is_causal():
    from repro.models.mla import init_mla, mla_fwd
    cfg = get_config("deepseek-v2-lite").reduced()
    p = init_mla(cfg, jax.random.PRNGKey(4))
    x = jax.random.normal(jax.random.PRNGKey(5), (1, S, cfg.d_model))
    pos = jnp.arange(S)[None]
    x2 = x.at[:, S // 2:].set(jax.random.normal(jax.random.PRNGKey(6),
                                                (1, S - S // 2, cfg.d_model)))
    y, y2 = mla_fwd(cfg, p, x, pos), mla_fwd(cfg, p, x2, pos)
    assert y.shape == x.shape
    np.testing.assert_allclose(y[:, :S // 2], y2[:, :S // 2], rtol=1e-5,
                               atol=1e-6)
    assert not np.allclose(y[:, S // 2:], y2[:, S // 2:])


def test_param_scale_sanity():
    """Full-size parameter counts are in the right ballpark (analytic)."""
    from repro.core.archcost import param_counts
    approx = {
        "internlm2-20b": 20e9, "qwen1.5-4b": 4e9, "gemma3-1b": 1.3e9,
        "grok-1-314b": 314e9, "rwkv6-1.6b": 1.6e9,
        "recurrentgemma-2b": 2.7e9, "llama-3.2-vision-90b": 90e9,
        "qwen1.5-32b": 32e9, "qwen2-moe-a2.7b": 14e9,
        "deepseek-v2-lite": 15.7e9,
    }
    for arch, want in approx.items():
        n, _ = param_counts(get_config(arch))
        assert 0.5 * want < n < 1.8 * want, (arch, n, want)
