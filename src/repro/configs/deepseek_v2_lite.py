"""deepseek-v2-lite [moe]: 27L d_model=2048 16H vocab=102400 —
multi-head latent attention (512-wide KV latent + 64-wide shared RoPE key,
no query compression; qk_nope 128, qk_rope 64, v_head 128), YaRN RoPE
(factor 40 from 4096 positions), layer 0 a dense SwiGLU of width 10944,
layers 1-26 MoE: 64 routed experts of width 1408, greedy top-6 over
softmax scores (not renormalized, scale 1), 2 shared experts
[arXiv:2405.04434; hf deepseek-ai/DeepSeek-V2-Lite config.json]."""
from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, YarnConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=1,
    d_ff=10944, vocab=102400, rope="standard", head_dim=192,
    norm_eps=1e-6, first_dense_layers=1,
    mla=MLAConfig(kv_rank=512, qk_nope_dim=128, qk_rope_dim=64,
                  v_head_dim=128),
    yarn=YarnConfig(factor=40.0, original_max_positions=4096,
                    beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                    mscale_all_dim=0.707),
    moe=MoEConfig(n_experts=64, top_k=6, d_expert=1408, n_shared_experts=2),
)
