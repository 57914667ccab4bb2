"""Operations and bytes of the MoE decoder, from a config file.

As for the dense decoder, with the feed-forward block replaced by a
float32 router and ``num_experts_per_tok`` SwiGLU experts per token.  A
step reads the experts that some token was routed to.  The program does
not report its routing, so the count takes the expected number of
distinct experts under uniform routing,
``E * (1 - (1 - k/E) ** live)`` per layer (31.997 of 32 at 32 live
slots, top-8 of 32).
"""
from __future__ import annotations

from bench.counts import dense
from bench.counts.dense import (BF16, attention_flops, kv_row_bytes,  # noqa: F401
                                padded_vocab)

F32 = 4


def _expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def _router_params(c: dict) -> int:
    return c["hidden_size"] * c["num_local_experts"]


def params(c: dict) -> int:
    d, L = c["hidden_size"], c["num_hidden_layers"]
    per_layer = (dense._attn_params(c) + _router_params(c)
                 + c["num_local_experts"] * _expert_params(c) + 2 * d)
    return L * per_layer + padded_vocab(c) * d + d


def matmul_params_per_token(c: dict) -> int:
    per_layer = (dense._attn_params(c) + _router_params(c)
                 + c["num_experts_per_tok"] * _expert_params(c))
    return (c["num_hidden_layers"] * per_layer
            + c["vocab_size"] * c["hidden_size"])


def decode_flops(c: dict, live: int, keys: int) -> int:
    return 2 * matmul_params_per_token(c) * live + attention_flops(c, keys)


def experts_read(c: dict, live: int) -> float:
    e, k = c["num_local_experts"], c["num_experts_per_tok"]
    return e * (1 - (1 - k / e) ** live)


def weight_bytes(c: dict, live: int) -> float:
    if not live:
        return 0
    per_layer = (BF16 * dense._attn_params(c) + F32 * _router_params(c)
                 + BF16 * experts_read(c, live) * _expert_params(c))
    return (c["num_hidden_layers"] * per_layer
            + BF16 * c["vocab_size"] * c["hidden_size"])


def decode_bytes(c: dict, live: int, keys: int) -> float:
    return weight_bytes(c, live) + kv_row_bytes(c) * (keys + live)
