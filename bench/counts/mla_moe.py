"""Operations and bytes of the DeepSeek-V3-style decoder (latent
attention, a leading dense layer, sigmoid-routed experts with shared
experts), from a config file, for one decode step in the absorbed form.

Per token, every layer multiplies through ``wq``, ``wkv_a``, ``wkv_b``
(as the query's absorption into latent space and the values' way out of
it) and ``wo``; the dense layers through their SwiGLU; the expert
layers through the float32 router and the shared experts, and through
``num_experts_per_tok`` routed experts of which a ``1 / ep_size`` share
lies on this chip; and the head over the real vocabulary.  Attention
reads each key's latent and rotary part once per head: scores
``kv_lora_rank + qk_rope_head_dim`` and values ``kv_lora_rank``
multiply-adds per key, head and layer.  A step reads the held experts
that some token was routed to, ``held * (1 - (1 - k/E) ** live)`` per
layer under uniform routing (7.66 of 8 at 32 live slots, top-6 of 64),
and the latent cache of the filled positions, 1,152 bytes a position a
layer.  Norms, RoPE and softmax are left out.
"""
from __future__ import annotations

from bench.weights.dense import padded_vocab

BF16, F32 = 2, 4


def _attn_params(c: dict) -> int:
    d, H, r = c["hidden_size"], c["num_attention_heads"], c["kv_lora_rank"]
    nope, rope, v = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"])
    return (d * H * (nope + rope) + d * (r + rope) + r * H * (nope + v)
            + H * v * d)


def _dense_ffn_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def _expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def _router_params(c: dict) -> int:
    return c["hidden_size"] * c["n_routed_experts"]


def held(c: dict) -> int:
    return c["n_routed_experts"] // c["ep_size"]


def _layers(c: dict):
    k = c["first_k_dense_replace"]
    return k, c["num_hidden_layers"] - k


def params(c: dict) -> int:
    """Parameters of the served tree: norms, the router's bias and the
    padded rows and columns of the embedding and the untied head
    included."""
    d = c["hidden_size"]
    n_dense, n_moe = _layers(c)
    attn = _attn_params(c) + c["kv_lora_rank"] + 2 * d
    moe = (_router_params(c) + c["n_routed_experts"]
           + (held(c) + c["n_shared_experts"]) * _expert_params(c))
    return (n_dense * (attn + _dense_ffn_params(c)) + n_moe * (attn + moe)
            + 2 * padded_vocab(c) * d + d)


def matmul_params_per_token(c: dict) -> float:
    """Weights one token multiplies through on this chip."""
    n_dense, n_moe = _layers(c)
    routed = (c["num_experts_per_tok"] / c["ep_size"]) * _expert_params(c)
    moe = _router_params(c) + c["n_shared_experts"] * _expert_params(c)
    return (c["num_hidden_layers"] * _attn_params(c)
            + n_dense * _dense_ffn_params(c) + n_moe * (moe + routed)
            + c["vocab_size"] * c["hidden_size"])


def attention_flops(c: dict, keys: int) -> int:
    """Absorbed scores and values for one query over ``keys`` positions,
    all layers."""
    r, rope = c["kv_lora_rank"], c["qk_rope_head_dim"]
    return (2 * c["num_hidden_layers"] * c["num_attention_heads"]
            * (2 * r + rope) * keys)


def decode_flops(c: dict, live: int, keys: int) -> float:
    return 2 * matmul_params_per_token(c) * live + attention_flops(c, keys)


def experts_read(c: dict, live: int) -> float:
    e, k = c["n_routed_experts"], c["num_experts_per_tok"]
    return held(c) * (1 - (1 - k / e) ** live)


def weight_bytes(c: dict, live: int) -> float:
    if not live:
        return 0
    n_dense, n_moe = _layers(c)
    moe = (F32 * (_router_params(c) + c["n_routed_experts"])
           + BF16 * (experts_read(c, live) + c["n_shared_experts"])
           * _expert_params(c))
    return (BF16 * c["num_hidden_layers"] * _attn_params(c)
            + BF16 * n_dense * _dense_ffn_params(c) + n_moe * moe
            + BF16 * c["vocab_size"] * c["hidden_size"])


def kv_row_bytes(c: dict) -> int:
    """The latent and the rotary key part of one position, every layer."""
    return (c["num_hidden_layers"]
            * (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * BF16)


def decode_bytes(c: dict, live: int, keys: int) -> float:
    """Weights once, the filled latent rows read, one new row per slot."""
    return weight_bytes(c, live) + kv_row_bytes(c) * (keys + live)
