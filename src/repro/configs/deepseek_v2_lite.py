"""deepseek-v2-lite [moe] — MLA kv_lora=512 with YaRN, one leading dense
layer, then 2 shared + 64 routed top-6 experts per layer
[arXiv:2405.04434; huggingface.co/deepseek-ai/DeepSeek-V2-Lite config.json]."""
from repro.configs.base import AttentionConfig, ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    source="https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json",
    num_layers=27,
    num_dense_layers=1,  # first_k_dense_replace
    d_model=2048,
    d_ff=10944,  # the dense layer's SwiGLU width
    vocab_size=102400,
    max_seq_len=163840,
    attention=AttentionConfig(kind="mla", num_heads=16, num_kv_heads=16,
                              head_dim=128, kv_lora_rank=512, q_lora_rank=None,
                              qk_rope_head_dim=64, qk_nope_head_dim=128,
                              v_head_dim=128, rope_theta=10000.0,
                              yarn_factor=40.0, yarn_original_max_position=4096,
                              yarn_beta_fast=32.0, yarn_beta_slow=1.0,
                              yarn_mscale=0.707, yarn_mscale_all_dim=0.707),
    # softmax over 64, greedy top-6, norm_topk_prob false, routed scale 1;
    # aux_loss_alpha 0.001 (the repo's batch-level balance form), no z-loss
    moe=MoEConfig(num_experts=64, top_k=6, d_expert_hidden=1408,
                  num_shared_experts=2, experts_held=64,
                  capacity_factor=1.25, gate_policy="softmax_topk",
                  renormalize=False, balance_loss_weight=0.001,
                  z_loss_weight=0.0),
    norm="rmsnorm",
    act="swiglu",
)
