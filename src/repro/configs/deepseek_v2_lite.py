"""deepseek-v2-lite [moe]: 27L d_model=2048 16H MLA (kv_lora_rank=512,
qk_nope=128, qk_rope=64, v=128, no q compression); layer 0 dense
(d_ff=10944), layers 1-26 MoE: 64 routed experts of width 1408, top-6
greedy softmax gates left unnormalized, plus 2 shared experts;
vocab=102400, untied head.  15 706 357 760 parameters, 2 661 023 744
active per token (matrices only; :func:`repro.core.archcost.param_counts`).
[hf:deepseek-ai/DeepSeek-V2-Lite config.json; arXiv:2405.04434]

Departures: ``rope_scaling`` (YaRN, factor 40 from 4096 positions) is
not applied — plain RoPE at ``rope_theta``, which changes no shape; the
softmax scale leaves out YaRN's ``mscale``; the load-balance auxiliary
loss is the repo's Switch-style one, not DeepSeek's sequence-wise
loss; routing keeps the repo's capacity factor (dropped tokens fall
through the residual); no decode cache (training and prefill only).
"""
from repro.models.common import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    arch_type="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=10944,                       # the one dense layer's MLP
    vocab_size=102400,
    layer_pattern="G",
    first_k_dense=1,
    num_experts=64,
    experts_per_token=6,
    moe_d_ff=1408,
    shared_expert_d_ff=2 * 1408,      # 2 shared experts, fused
    norm_topk_prob=False,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=10_000.0,
    source="hf:deepseek-ai/DeepSeek-V2-Lite; arXiv:2405.04434",
).validate()
