"""deepseek-v2-lite [moe]: multi-head latent attention (16 heads, a
512-wide latent and a 64-wide shared rope key, YaRN) and fine-grained
experts (64 routed SwiGLU of 1408, top-6 by a softmax gate without
renormalisation, 2 shared fused into one of 2816); the first layer is a
dense SwiGLU of 10944.  [arXiv:2405.04434;
hf deepseek-ai/DeepSeek-V2-Lite config.json]"""
from repro.models.config import ModelConfig, MoEConfig, YarnRope

CONFIG = ModelConfig(
    name="deepseek-v2-lite", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=10944, vocab=102400, head_dim=192, act="swiglu",
    norm_eps=1e-6, rope_theta=10_000.0,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128,
    yarn=YarnRope(factor=40.0, beta_fast=32.0, beta_slow=1.0,
                  original_max_position_embeddings=4096,
                  mscale=0.707, mscale_all_dim=0.707),
    moe=MoEConfig(n_experts=64, top_k=6, d_expert=1408,
                  n_shared=2, d_shared=2816,
                  first_dense=1, d_first_dense=10944, norm_topk=False),
)

REDUCED = ModelConfig(
    name="deepseek-v2-lite-reduced", family="moe",
    n_layers=3, d_model=64, n_heads=4, n_kv_heads=4,
    d_ff=192, vocab=256, head_dim=24, act="swiglu",
    norm_eps=1e-6, rope_theta=10_000.0,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16,
    yarn=YarnRope(factor=40.0, beta_fast=32.0, beta_slow=1.0,
                  original_max_position_embeddings=64,
                  mscale=0.707, mscale_all_dim=0.707),
    moe=MoEConfig(n_experts=16, top_k=4, d_expert=32,
                  n_shared=2, d_shared=64,
                  first_dense=1, d_first_dense=192, norm_topk=False),
)
