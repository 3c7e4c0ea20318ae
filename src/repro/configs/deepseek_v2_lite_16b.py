"""deepseek-v2-lite-16b [moe] — MLA kv_lora=512 + 2 shared / 64 routed
top-6 experts (arXiv:2405.04434; huggingface.co/deepseek-ai/DeepSeek-V2-Lite
config.json).

Layer 0 is dense (first_k_dense_replace 1, SwiGLU 10944); layers 1–26 hold
2 shared and 64 routed experts of width 1408, routed by the top 6 of a
softmax over all 64 with no renormalisation (norm_topk_prob false, scaling
factor 1).  RoPE on the 64 rotary dims uses YaRN (factor 40 over 4096
original positions).  The registry entry holds all 64 experts; a chip's
share of them is set with ``experts_first`` / ``experts_held``."""
from repro.models.config import (MLAConfig, ModelConfig, MoEConfig,
                                 YarnConfig)

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b", family="moe",
    num_layers=27, d_model=2048, num_heads=16, num_kv_heads=16,
    d_ff=10944, vocab_size=102400,
    block_pattern=("mla_moe",), first_dense=1,
    moe=MoEConfig(num_experts=64, top_k=6, d_expert=1408, num_shared=2,
                  norm_topk_prob=False),
    mla=MLAConfig(kv_lora_rank=512, rope_head_dim=64, nope_head_dim=128,
                  v_head_dim=128),
    rope_scaling=YarnConfig(factor=40.0, original_max_position=4096,
                            beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                            mscale_all_dim=0.707),
)
