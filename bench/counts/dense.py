"""Operations and bytes of the dense GQA decoder, from a config file.

What the algorithm needs, not what a program happens to do: matmul
FLOPs (2 per multiply-add) of the real vocabulary, attention over the
filled positions only, weights read once per step, and the KV rows of
the filled positions read once.  Norms, RoPE and softmax are left out
(under 1% of the FLOPs at these widths).
"""
from __future__ import annotations

from bench.weights.dense import padded_vocab

BF16 = 2


def _attn_params(c: dict) -> int:
    d, hd = c["hidden_size"], c["head_dim"]
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    bias = (q + 2 * kv) if c["attention_bias"] else 0
    return d * (q + 2 * kv) + q * d + bias


def _ffn_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def params(c: dict) -> int:
    """Parameters of the served tree, padded embedding rows (and an
    untied head's padded columns) included."""
    d, L = c["hidden_size"], c["num_hidden_layers"]
    per_layer = _attn_params(c) + _ffn_params(c) + 2 * d
    heads = 1 if c["tie_word_embeddings"] else 2
    return L * per_layer + heads * padded_vocab(c) * d + d


def matmul_params_per_token(c: dict) -> int:
    """Weights one token multiplies through: every layer and the LM head
    (tied or not) over the real vocabulary."""
    return (c["num_hidden_layers"] * (_attn_params(c) + _ffn_params(c))
            + c["vocab_size"] * c["hidden_size"])


def attention_flops(c: dict, keys: int) -> int:
    """QK^T and PV for one query over ``keys`` positions, all layers."""
    return (4 * c["num_hidden_layers"] * c["num_attention_heads"]
            * c["head_dim"] * keys)


def decode_flops(c: dict, live: int, keys: int) -> int:
    """One decode step of ``live`` slots whose queries see ``keys``
    positions in all (summed over the slots)."""
    return 2 * matmul_params_per_token(c) * live + attention_flops(c, keys)


def weight_bytes(c: dict, live: int) -> int:
    return BF16 * matmul_params_per_token(c) if live else 0


def kv_row_bytes(c: dict) -> int:
    """K and V of one position in every layer."""
    return (2 * c["num_hidden_layers"] * c["num_key_value_heads"]
            * c["head_dim"] * BF16)


def decode_bytes(c: dict, live: int, keys: int) -> int:
    """Weights once, the filled KV rows read, and one new row per slot."""
    return weight_bytes(c, live) + kv_row_bytes(c) * (keys + live)
