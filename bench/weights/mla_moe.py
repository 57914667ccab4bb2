"""Parameter tree of the program's DeepSeek-V3-style decoder, from a
config file: latent attention (MLA) in every layer;
``first_k_dense_replace`` leading layers with a SwiGLU of
``intermediate_size`` (the ``dense_layers`` stack), then expert layers
(``layers``) with a float32 router over all ``n_routed_experts`` and
its correction bias, the ``n_routed_experts / ep_size`` experts this
chip holds, and the shared experts as one SwiGLU of
``n_shared_experts * moe_intermediate_size``; an untied output head."""
from __future__ import annotations

from typing import Dict

from bench.lib.weights import Leaf, Path
from bench.weights.dense import global_leaves


def stacks(c: dict) -> Dict[str, int]:
    k = c["first_k_dense_replace"]
    return {"dense_layers": k, "layers": c["num_hidden_layers"] - k}


def program_keys(c: dict) -> dict:
    """The program's fields for latent attention and the expert layer.
    The router is the program's sigmoid one only with one group, the
    chosen weights renormalised and the bias choosing (noaux_tc)."""
    fixed = {k: c[k] for k in ("n_group", "topk_group", "norm_topk_prob",
                               "topk_method", "q_lora_rank")}
    if fixed != {"n_group": 1, "topk_group": 1, "norm_topk_prob": True,
                 "topk_method": "noaux_tc", "q_lora_rank": None}:
        raise SystemExit(f"no program router or attention for {fixed}")
    return {"n_experts": c["n_routed_experts"],
            "kv_lora_rank": c["kv_lora_rank"],
            "qk_rope_dim": c["qk_rope_head_dim"],
            "qk_nope_dim": c["qk_nope_head_dim"],
            "v_head_dim": c["v_head_dim"],
            "moe_d_ff": c["moe_intermediate_size"],
            "n_shared_experts": c["n_shared_experts"],
            "n_dense_layers": c["first_k_dense_replace"],
            "router": c["scoring_func"],
            "routed_scaling": c["routed_scaling_factor"],
            "ep_size": c["ep_size"]}


def attention_leaves(c: dict, stack: str) -> Dict[Path, Leaf]:
    d, H, r = c["hidden_size"], c["num_attention_heads"], c["kv_lora_rank"]
    nope, rope, v = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"])
    dt = c["torch_dtype"]
    return {
        (stack, "ln1"): Leaf((d,), "float32", 0.05, True),
        (stack, "ln2"): Leaf((d,), "float32", 0.05, True),
        (stack, "attn", "wq"): Leaf((d, H * (nope + rope)), dt, d ** -0.5,
                                    True),
        (stack, "attn", "wkv_a"): Leaf((d, r + rope), dt, d ** -0.5, True),
        (stack, "attn", "kv_norm"): Leaf((r,), "float32", 0.05, True),
        (stack, "attn", "wkv_b"): Leaf((r, H * (nope + v)), dt, r ** -0.5,
                                       True),
        (stack, "attn", "wo"): Leaf((H * v, d), dt, (H * v) ** -0.5, True),
    }


def layout(c: dict) -> Dict[Path, Leaf]:
    d, ff, dt = c["hidden_size"], c["intermediate_size"], c["torch_dtype"]
    mff, e = c["moe_intermediate_size"], c["n_routed_experts"]
    held = e // c["ep_size"]
    sff = c["n_shared_experts"] * mff
    out = dict(global_leaves(c))
    for stack in stacks(c):
        out.update(attention_leaves(c, stack))
    out.update({
        ("dense_layers", "mlp", "w_gate"): Leaf((d, ff), dt, d ** -0.5, True),
        ("dense_layers", "mlp", "w_up"): Leaf((d, ff), dt, d ** -0.5, True),
        ("dense_layers", "mlp", "w_down"): Leaf((ff, d), dt, ff ** -0.5,
                                                True),
        ("layers", "moe", "router"): Leaf((d, e), "float32", d ** -0.5, True),
        ("layers", "moe", "router_bias"): Leaf((e,), "float32", 0.1, True),
        ("layers", "moe", "w_gate"): Leaf((held, d, mff), dt, d ** -0.5,
                                          True),
        ("layers", "moe", "w_up"): Leaf((held, d, mff), dt, d ** -0.5, True),
        ("layers", "moe", "w_down"): Leaf((held, mff, d), dt, mff ** -0.5,
                                          True),
        ("layers", "moe", "shared", "w_gate"): Leaf((d, sff), dt, d ** -0.5,
                                                    True),
        ("layers", "moe", "shared", "w_up"): Leaf((d, sff), dt, d ** -0.5,
                                                  True),
        ("layers", "moe", "shared", "w_down"): Leaf((sff, d), dt,
                                                    sff ** -0.5, True)})
    return out
