"""qwen3-moe-30b-a3b [moe] — 128 experts top-8, fine-grained experts.

48L d_model=2048 32H (GQA kv=4) d_ff=768 vocab=151936  [hf:Qwen/Qwen3-30B-A3B]
Per-head q/k RMSNorm, no attention bias, untied head, rms_norm_eps 1e-6,
top-8 probabilities renormalised (``norm_topk_prob``).
"""
from repro.configs.base import ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="qwen3-moe-30b-a3b",
    family="moe",
    n_layers=48,
    d_model=2048,
    n_heads=32,
    n_kv_heads=4,
    d_ff=768,
    vocab_size=151936,
    head_dim=128,
    moe=MoEConfig(num_experts=128, top_k=8, moe_every=1, expert_d_ff=768),
    rope_theta=1e6,
    rms_eps=1e-6,
    qk_norm=True,
    qkv_bias=False,
    tie_embeddings=False,
)
