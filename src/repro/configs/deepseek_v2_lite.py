"""deepseek-v2-lite [moe] — MLA, one leading dense layer, DeepSeekMoE.

27L d_model=2048 16H, MLA (kv_lora_rank 512, qk 128 nope + 64 rope, v 128,
no q-LoRA, YaRN rope x40), first layer dense (d_ff 10944), then 64 routed
experts of width 1408 (softmax greedy top-6, not renormalised) and 2 shared
experts, vocab 102400 untied  [arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite]
"""
from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, YaRNScaling

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=10944,
    vocab_size=102400,
    rope_theta=10_000.0,
    norm_eps=1e-6,
    mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128,
                  rope_scaling=YaRNScaling(
                      factor=40.0, original_max_position_embeddings=4096,
                      beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                      mscale_all_dim=0.707)),
    first_dense_layers=1,
    # aux_loss_weight: config.json's aux_loss_alpha is not among the
    # catalog's keys; 0.001 is DeepseekV2Config's default
    moe=MoEConfig(n_experts=64, top_k=6, d_expert=1408, n_shared=2,
                  aux_loss_weight=0.001, dispatch_impl="dropless"),
    logit_chunk=8192,
)
