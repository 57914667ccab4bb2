"""Parameter tree of the program's dense GQA decoder, from a config file.

Paths, shapes and dtypes are those the program's ``init_model`` gives
(``run.py`` checks them against its ``eval_shape`` before every run).
Standard deviations: 1/sqrt(fan-in) for projections and an untied
output head, 0.02 for the embedding, and small non-zero norm scales and
biases so that the comparison with the reference covers them too.
"""
from __future__ import annotations

from typing import Dict

from bench.lib.weights import Leaf, Path


def padded_vocab(c: dict) -> int:
    """The program pads the embedding's rows to a multiple of 256."""
    return -(-c["vocab_size"] // 256) * 256


def attention_leaves(c: dict) -> Dict[Path, Leaf]:
    d, hd = c["hidden_size"], c["head_dim"]
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    dt = c["torch_dtype"]
    out = {
        ("layers", "ln1"): Leaf((d,), "float32", 0.05, True),
        ("layers", "ln2"): Leaf((d,), "float32", 0.05, True),
        ("layers", "attn", "wq"): Leaf((d, q), dt, d ** -0.5, True),
        ("layers", "attn", "wk"): Leaf((d, kv), dt, d ** -0.5, True),
        ("layers", "attn", "wv"): Leaf((d, kv), dt, d ** -0.5, True),
        ("layers", "attn", "wo"): Leaf((q, d), dt, q ** -0.5, True),
    }
    if c["attention_bias"]:
        out[("layers", "attn", "bq")] = Leaf((q,), dt, 0.05, True)
        out[("layers", "attn", "bk")] = Leaf((kv,), dt, 0.05, True)
        out[("layers", "attn", "bv")] = Leaf((kv,), dt, 0.05, True)
    return out


def global_leaves(c: dict) -> Dict[Path, Leaf]:
    """The embedding, the final norm and, where the file unties them, the
    output head (d, padded vocabulary), as the program stores it."""
    d, dt = c["hidden_size"], c["torch_dtype"]
    out = {("embed",): Leaf((padded_vocab(c), d), dt, 0.02),
           ("ln_f",): Leaf((d,), "float32", 0.05)}
    if not c["tie_word_embeddings"]:
        out[("unembed",)] = Leaf((d, padded_vocab(c)), dt, d ** -0.5)
    return out


def layout(c: dict) -> Dict[Path, Leaf]:
    d, ff, dt = c["hidden_size"], c["intermediate_size"], c["torch_dtype"]
    return {**global_leaves(c), **attention_leaves(c),
            ("layers", "mlp", "w_gate"): Leaf((d, ff), dt, d ** -0.5, True),
            ("layers", "mlp", "w_up"): Leaf((d, ff), dt, d ** -0.5, True),
            ("layers", "mlp", "w_down"): Leaf((ff, d), dt, ff ** -0.5, True)}
