"""Parameter tree of the program's MoE decoder (top-k routed SwiGLU
experts after GQA attention), from a config file."""
from __future__ import annotations

from typing import Dict

from bench.lib.weights import Leaf, Path
from bench.weights.dense import attention_leaves, global_leaves


def layout(c: dict) -> Dict[Path, Leaf]:
    d, ff, dt = c["hidden_size"], c["intermediate_size"], c["torch_dtype"]
    e = c["num_local_experts"]
    return {**global_leaves(c), **attention_leaves(c),
            ("layers", "moe", "router"): Leaf((d, e), "float32", d ** -0.5,
                                              True),
            ("layers", "moe", "w_gate"): Leaf((e, d, ff), dt, d ** -0.5, True),
            ("layers", "moe", "w_up"): Leaf((e, d, ff), dt, d ** -0.5, True),
            ("layers", "moe", "w_down"): Leaf((e, ff, d), dt, ff ** -0.5,
                                              True)}
