"""Two layer stacks of their own lengths: ``first_k_dense_replace``
leading dense layers (SwiGLU of ``intermediate_size``), then the expert
layers (``n_routed_experts`` of ``moe_intermediate_size``), as a
DeepSeek-V3-style file states them; GQA attention in both, and an
untied output head."""
from typing import Dict

from bench.lib.weights import Leaf, Path
from bench.weights.dense import attention_leaves, global_leaves


def stacks(c: dict) -> Dict[str, int]:
    k = c["first_k_dense_replace"]
    return {"dense_layers": k, "layers": c["num_hidden_layers"] - k}


def layout(c: dict) -> Dict[Path, Leaf]:
    d, dt = c["hidden_size"], c["torch_dtype"]
    ff, mff, e = (c["intermediate_size"], c["moe_intermediate_size"],
                  c["n_routed_experts"])
    out = dict(global_leaves(c))
    for stack in stacks(c):
        out.update({(stack,) + k[1:]: v
                    for k, v in attention_leaves(c).items()})
    out.update({
        ("dense_layers", "mlp", "w_gate"): Leaf((d, ff), dt, d ** -0.5, True),
        ("dense_layers", "mlp", "w_up"): Leaf((d, ff), dt, d ** -0.5, True),
        ("dense_layers", "mlp", "w_down"): Leaf((ff, d), dt, ff ** -0.5,
                                                True),
        ("layers", "moe", "router"): Leaf((d, e), "float32", d ** -0.5, True),
        ("layers", "moe", "w_gate"): Leaf((e, d, mff), dt, d ** -0.5, True),
        ("layers", "moe", "w_up"): Leaf((e, d, mff), dt, d ** -0.5, True),
        ("layers", "moe", "w_down"): Leaf((e, mff, d), dt, mff ** -0.5,
                                          True)})
    return out
