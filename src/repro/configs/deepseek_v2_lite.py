"""deepseek-v2-lite [mla_moe] — 27L d_model=2048, 16 heads of multi-head
latent attention (no query LoRA; q 128 no-rope + 64 rope per head, a
512-wide latent c_kv and one shared 64-wide rope key, per-head k 128 and
v 128 from the latent), YaRN rope (factor 40 over 4096 positions), layer 0
a dense SwiGLU of 10944, layers 1-26 DeepSeekMoE: 64 routed SwiGLU experts
of 1408, 6 per token from a float32 softmax over all 64, not renormalised,
and 2 shared experts; vocab 102400, untied head.  All 64 experts are held
here; a deployment's chip holds its share (``experts_first`` /
``experts_held``).
[hf:deepseek-ai/DeepSeek-V2-Lite config.json; arXiv:2405.04434]
"""
from repro.models.config import ModelConfig

ARCH_ID = "deepseek-v2-lite"

CONFIG = ModelConfig(
    name=ARCH_ID,
    layout="mla_moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    head_dim=192,                # qk_nope_head_dim + qk_rope_head_dim
    d_ff=10944,                  # the leading dense layer's MLP
    vocab_size=102400,
    n_experts=64,
    top_k=6,
    moe_d_ff=1408,
    n_shared_experts=2,
    norm_topk_prob=False,
    first_k_dense=1,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=10000.0,
    yarn_factor=40.0,
    yarn_original_len=4096,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
    norm_eps=1e-6,
    max_seq_len=163840,
)
