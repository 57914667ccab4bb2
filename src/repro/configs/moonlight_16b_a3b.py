"""moonlight-16b-a3b [moe, deepseek_v3]: 27L d_model=2048 16H, latent
attention (kv_lora_rank 512, qk nope 128 + rope 64, v 128, no q-LoRA),
one leading dense layer (SwiGLU 11264), then 26 expert layers: 64 routed
experts of 1408, top-6 by sigmoid score plus a correction bias (noaux_tc,
one group), renormalised and scaled by 2.446, and 2 shared experts;
untied head over 163840; rope_theta 50000, rms_norm_eps 1e-5.
[hf:moonshotai/Moonlight-16B-A3B; hf]

Deployment: every expert layer is divided over 8 chips (``ep_size`` 8,
the source's 1), and this chip holds routed experts 0-7 of 64 with both
shared experts; attention, the dense layer, the embedding and the head
are replicated on each chip (data-parallel attention).  All 27 layers
and the whole vocabulary are kept."""
from repro.models.common import ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b", arch_kind="moe", n_layers=27, d_model=2048,
    n_heads=16, n_kv_heads=16, d_ff=11264, vocab=163840, head_dim=128,
    rope_theta=50000.0, norm_eps=1e-5, tie_embeddings=False,
    n_experts=64, top_k=6, moe_d_ff=1408, n_shared_experts=2,
    n_dense_layers=1, router="sigmoid", routed_scaling=2.446, ep_size=8,
    kv_lora_rank=512, qk_rope_dim=64, qk_nope_dim=128, v_head_dim=128)

SMOKE = ModelConfig(
    name="moonlight-16b-a3b-smoke", arch_kind="moe", n_layers=3,
    d_model=64, n_heads=4, n_kv_heads=4, d_ff=96, vocab=512, head_dim=16,
    rope_theta=50000.0, norm_eps=1e-5, tie_embeddings=False,
    n_experts=8, top_k=2, moe_d_ff=32, n_shared_experts=1,
    n_dense_layers=1, router="sigmoid", routed_scaling=2.446, ep_size=2,
    kv_lora_rank=32, qk_rope_dim=8, qk_nope_dim=16, v_head_dim=16)
