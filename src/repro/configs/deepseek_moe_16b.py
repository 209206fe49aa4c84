"""deepseek-moe-16b [moe]: 2 shared + 64 routed top-6, fine-grained,
first layer dense; the gate's top-6 weights are not renormalised
(``norm_topk_prob: false``). [arXiv:2401.06066; hf]"""
from repro.models.config import ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="deepseek-moe-16b", family="moe",
    n_layers=28, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=1408, vocab=102400, head_dim=128, act="swiglu",
    moe=MoEConfig(n_experts=64, top_k=6, d_expert=1408,
                  n_shared=2, d_shared=2816,
                  first_dense=1, d_first_dense=10944, norm_topk=False),
)

REDUCED = ModelConfig(
    name="deepseek-moe-16b-reduced", family="moe",
    n_layers=3, d_model=64, n_heads=4, n_kv_heads=4,
    d_ff=64, vocab=256, head_dim=16, act="swiglu",
    moe=MoEConfig(n_experts=8, top_k=2, d_expert=64,
                  n_shared=1, d_shared=128,
                  first_dense=1, d_first_dense=256, norm_topk=False),
)
