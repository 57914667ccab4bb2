"""Step factories: train_step (grad-accumulation microbatching, remat,
AdamW) and serve_step (one token a slot, and optionally one slot's
prompt chunk), arch-dispatch included.

These are the functions the launcher jits with explicit in/out shardings;
everything inside is GSPMD-shardable einsum/scan code.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import ModelConfig
from repro.models.transformer import (decode_step, forward, loss_fn,
                                      whisper_decode_step, whisper_loss_fn)
from repro.optim.adamw import AdamWConfig, AdamWState, apply_updates


def arch_loss_fn(cfg: ModelConfig) -> Callable:
    return whisper_loss_fn if cfg.arch_kind == "encdec" else loss_fn


def make_train_step(cfg: ModelConfig, opt_cfg: AdamWConfig,
                    microbatches: int = 1,
                    grad_dtype=jnp.float32,
                    data_axes: Tuple[str, ...] = ("data",)) -> Callable:
    """(params, opt_state, batch) -> (params, opt_state, metrics).

    With microbatches > 1 the batch's leading dim is split and gradients
    accumulate in ``grad_dtype`` across a lax.scan — bounding activation
    memory at one microbatch (straggler-friendly: each microbatch is an
    independent unit of work).

    The split is interleaved — (B,) -> (B/M, M) -> swap — so the data-
    parallel sharding of B stays on the *per-microbatch* batch dim; a
    naive (M, B/M) reshape would put it on the scanned dim, which lax.scan
    cannot iterate sharded (XLA would replicate the whole batch).
    """
    base_loss = arch_loss_fn(cfg)
    from repro.distributed.hints import hint

    def _split(x):
        b = x.shape[0]
        y = x.reshape(b // microbatches, microbatches, *x.shape[1:])
        y = jnp.swapaxes(y, 0, 1)
        # no-op without a mesh in context (single-device smoke tests)
        return hint(y, None, "batch", *([None] * (x.ndim - 1)))

    def train_step(params, opt_state: AdamWState, batch):
        if microbatches <= 1:
            loss, grads = jax.value_and_grad(base_loss)(params, cfg, batch)
        else:
            mb = jax.tree.map(_split, batch)

            def acc(carry, mbatch):
                gsum, lsum = carry
                l, g = jax.value_and_grad(base_loss)(params, cfg, mbatch)
                gsum = jax.tree.map(
                    lambda a, b: a + b.astype(grad_dtype), gsum, g)
                return (gsum, lsum + l), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, grad_dtype), params)
            (gsum, lsum), _ = jax.lax.scan(acc, (zeros, jnp.zeros((), jnp.float32)), mb)
            grads = jax.tree.map(lambda g: (g / microbatches), gsum)
            loss = lsum / microbatches
        new_params, new_state = apply_updates(opt_cfg, params, grads,
                                              opt_state)
        metrics = {"loss": loss, "step": new_state.step,
                   "grad_norm": _global_norm(grads)}
        return new_params, new_state, metrics

    return train_step


def _global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


def make_prefill_step(cfg: ModelConfig) -> Callable:
    """(params, batch) -> logits — the inference-prefill cell."""
    def prefill_step(params, batch):
        if cfg.arch_kind == "encdec":
            from repro.models.transformer import whisper_forward
            return whisper_forward(params, cfg, batch["frames"],
                                   batch["tokens"])
        logits, _ = forward(params, cfg, batch["tokens"],
                            patch_embeds=batch.get("patch_embeds"))
        return logits

    return prefill_step


def make_serve_step(cfg: ModelConfig) -> Callable:
    """(params, tokens, cache, index) -> (next_tokens (B, 1), cache).

    One new token a slot against a seq_len KV cache (greedy argmax
    sampling).  ``tokens`` is (B, 1), or ``transformer.ChunkedTokens``
    (a prompt chunk of one slot beside the slots' tokens; that slot's
    sample is the chunk's); ``index`` the (B,) or shared cursor.
    """
    def serve_step(params, tokens, cache, index):
        if cfg.arch_kind == "encdec":
            logits, cache = whisper_decode_step(params, cfg, tokens, cache,
                                                index)
        else:
            logits, cache = decode_step(params, cfg, tokens, cache, index)
        with jax.named_scope("sample"):
            nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        return nxt[:, None], cache

    return serve_step


def pick_microbatches(cfg: ModelConfig, global_batch: int,
                      dp_size: int) -> int:
    """Accumulation steps so one microbatch is ~1 sample per data shard
    for the big dense models (activation memory bound), fewer for small."""
    per_shard = max(1, global_batch // max(1, dp_size))
    if (cfg.d_model >= 4096 or cfg.n_layers >= 40
            or cfg.arch_kind == "hybrid"):    # fp32 recurrence states
        return per_shard                      # 1 sample/shard/microbatch
    if cfg.d_model >= 2048:
        return max(1, per_shard // 2)
    return max(1, per_shard // 4)
