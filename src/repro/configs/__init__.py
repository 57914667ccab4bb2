"""Architecture registry: the 10 assigned (arch x shape) configs.

``get_config(arch_id, smoke=False)`` returns the exact published config
(or its reduced smoke sibling); ``input_specs(cfg, shape)`` returns
jax.ShapeDtypeStruct stand-ins for every model input of that cell —
weak-type-correct, shardable, no device allocation.
"""
from __future__ import annotations

import importlib
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.models.common import SHAPES, ModelConfig, ShapeSpec

ARCHS = {
    "qwen2.5-3b": "qwen2_5_3b",
    "qwen1.5-32b": "qwen1_5_32b",
    "phi3-medium-14b": "phi3_medium_14b",
    "gemma3-4b": "gemma3_4b",
    "recurrentgemma-2b": "recurrentgemma_2b",
    "rwkv6-1.6b": "rwkv6_1_6b",
    "whisper-medium": "whisper_medium",
    "granite-moe-1b-a400m": "granite_moe_1b_a400m",
    "moonlight-16b-a3b": "moonlight_16b_a3b",
    "qwen2-vl-2b": "qwen2_vl_2b",
}

#: archs with sub-quadratic long-context support: these run long_500k.
#: Pure full-attention archs skip it (see DESIGN.md Arch-applicability).
SUBQUADRATIC = {"gemma3-4b", "recurrentgemma-2b", "rwkv6-1.6b"}


def get_config(arch_id: str, smoke: bool = False) -> ModelConfig:
    mod = importlib.import_module(f"repro.configs.{ARCHS[arch_id]}")
    return mod.SMOKE if smoke else mod.CONFIG


def cell_supported(arch_id: str, shape_name: str) -> bool:
    """Is this (arch x shape) cell runnable?  (40 cells; 7 documented skips)"""
    if shape_name == "long_500k":
        return arch_id in SUBQUADRATIC
    return True


def input_specs(cfg: ModelConfig, shape: ShapeSpec) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for a train/prefill step's inputs."""
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    f32 = jnp.float32
    specs: Dict[str, Any] = {
        "tokens": jax.ShapeDtypeStruct((B, S), i32),
        "labels": jax.ShapeDtypeStruct((B, S), i32),
    }
    if cfg.arch_kind == "vlm":
        specs["patch_embeds"] = jax.ShapeDtypeStruct(
            (B, cfg.n_patches, cfg.d_model), f32)
    if cfg.arch_kind == "encdec":
        specs["frames"] = jax.ShapeDtypeStruct(
            (B, cfg.enc_frames, cfg.d_model), f32)
    return specs


def decode_input_specs(cfg: ModelConfig, shape: ShapeSpec) -> Dict[str, Any]:
    """Stand-ins for one serve_step: one new token + a seq_len KV cache,
    in the form ``init_cache`` stores (whisper: its own (L, B, T, Hkv,
    hd) self-attention cache beside the encoded audio)."""
    from repro.models.transformer import init_cache

    B, S = shape.global_batch, shape.seq_len
    cache = jax.eval_shape(lambda: init_cache(cfg, B, S))
    if cfg.arch_kind == "encdec":
        cache = {
            "enc": jax.ShapeDtypeStruct((B, cfg.enc_frames, cfg.d_model),
                                        cfg.dtype),
            "k": jax.ShapeDtypeStruct((cfg.n_layers, B, S, cfg.n_kv_heads,
                                       cfg.hd), cfg.dtype),
            "v": jax.ShapeDtypeStruct((cfg.n_layers, B, S, cfg.n_kv_heads,
                                       cfg.hd), cfg.dtype),
        }
    return {
        "tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32),
        "cache": cache,
        "index": jax.ShapeDtypeStruct((), jnp.int32),
    }


__all__ = ["ARCHS", "SHAPES", "SUBQUADRATIC", "cell_supported",
           "decode_input_specs", "get_config", "input_specs"]
