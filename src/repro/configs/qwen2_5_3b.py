"""qwen2.5-3b [dense]: 36L d_model=2048 16H (GQA kv=2) d_ff=11008
vocab=151936 — GQA, QKV bias, tied embeddings, rope_theta 1e6.
[hf:Qwen/Qwen2.5-3B config.json]"""
from repro.models.common import ModelConfig

CONFIG = ModelConfig(
    name="qwen2.5-3b", arch_kind="dense", n_layers=36, d_model=2048,
    n_heads=16, n_kv_heads=2, d_ff=11008, vocab=151936, head_dim=128,
    qkv_bias=True, rope_theta=1e6)

SMOKE = ModelConfig(
    name="qwen2.5-3b-smoke", arch_kind="dense", n_layers=2, d_model=64,
    n_heads=4, n_kv_heads=2, d_ff=128, vocab=512, head_dim=16,
    qkv_bias=True)
