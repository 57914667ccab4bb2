"""LM assembly: dense / MoE / local-global / hybrid / RWKV / enc-dec / VLM.

Uniform-layer families (dense, moe, vlm, rwkv) stack per-layer params along
a leading axis and `lax.scan` over layers with remat — required for the
64-layer configs to compile fast and keep activation memory at one layer.
A config with leading dense layers (``n_dense_layers``) has two stacks,
``dense_layers`` then ``layers``, scanned in turn.
The hybrid (RecurrentGemma) family scans over its repeating block pattern.

Public entry points (all pure):
    init_model(key, cfg)                     -> params
    forward(params, cfg, batch)              -> logits        (train/prefill)
    loss_fn(params, cfg, batch)              -> scalar loss
    init_cache(cfg, batch, max_len)          -> cache
    decode_step(params, cfg, tokens, cache, index) -> (logits, cache)

``decode_step``'s tokens may be ``ChunkedTokens``: a prompt chunk of
one slot rides behind the one-token rows (``takes_chunks``).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.hints import hint

from .common import (ModelConfig, Params, cross_entropy_loss, dense_init,
                     rms_norm, sinusoidal_positions)
from .layers import (Chunk, attention, cross_attention, gelu_mlp,
                     init_attention, init_gelu_mlp, init_mla, init_moe,
                     init_swiglu, mla_attention, moe_ffn, swiglu)
from .rglru import init_recurrent_block, recurrent_block
from .rwkv6 import (channel_mix, init_channel_mix, init_time_mix, time_mix)

BIG_WINDOW = 1 << 30   # "global" attention sentinel


def _mask_pad_vocab(logits: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Neutralize the padded embedding rows (softmax- and argmax-safe)."""
    if cfg.padded_vocab == cfg.vocab:
        return logits
    ids = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    return jnp.where(ids < cfg.vocab, logits,
                     jnp.asarray(-1e30, logits.dtype))


# ---------------------------------------------------------------------------
# layer windows (gemma3-style local:global patterns)
# ---------------------------------------------------------------------------

def layer_windows(cfg: ModelConfig) -> jnp.ndarray:
    """(n_layers,) int32 attention window per layer."""
    return jnp.asarray(static_layer_windows(cfg), jnp.int32)


def static_layer_windows(cfg: ModelConfig):
    """Python-level per-layer windows (static: enables sliced attention)."""
    if cfg.local_window <= 0:
        return [BIG_WINDOW] * cfg.n_layers
    w = []
    for l in range(cfg.n_layers):
        is_global = cfg.global_every > 0 and (l + 1) % cfg.global_every == 0
        w.append(BIG_WINDOW if is_global else cfg.local_window)
    return w


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def _init_decoder_layer(key: jax.Array, cfg: ModelConfig,
                        dense: bool = False) -> Params:
    """One layer; ``dense``: a leading layer's SwiGLU in place of the
    experts."""
    ks = jax.random.split(key, 4)
    p: Params = {"ln1": jnp.zeros((cfg.d_model,), jnp.float32),
                 "ln2": jnp.zeros((cfg.d_model,), jnp.float32)}
    if cfg.arch_kind == "rwkv":
        p["tmix"] = init_time_mix(ks[0], cfg)
        p["cmix"] = init_channel_mix(ks[1], cfg)
        return p
    p["attn"] = (init_mla if cfg.kv_lora_rank else init_attention)(ks[0], cfg)
    if cfg.n_experts and not dense:
        p["moe"] = init_moe(ks[1], cfg)
    else:
        p["mlp"] = init_swiglu(ks[1], cfg)
    return p


@functools.partial(jax.jit, static_argnames=("cfg",))
def init_model(key: jax.Array, cfg: ModelConfig) -> Params:
    """Seeded random weights, built as one jitted program: eagerly, the
    per-layer trees and their stacked copy would all sit on the device
    at once (two copies of the weights at the peak)."""
    if cfg.arch_kind == "encdec":
        return _init_whisper(key, cfg)
    ks = jax.random.split(key, cfg.n_layers + 3)
    params: Params = {
        "embed": dense_init(ks[0], (cfg.padded_vocab, cfg.d_model),
                            cfg.dtype, scale=0.02),
        "ln_f": jnp.zeros((cfg.d_model,), jnp.float32),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = dense_init(ks[1], (cfg.d_model, cfg.padded_vocab),
                                       cfg.dtype)
    if cfg.arch_kind == "vlm":
        params["patch_proj"] = dense_init(ks[2], (cfg.d_model, cfg.d_model),
                                          cfg.dtype)
    if cfg.arch_kind == "hybrid":
        layers = []
        for l in range(cfg.n_layers):
            kind = cfg.block_pattern[l % len(cfg.block_pattern)]
            kl = ks[3 + l]
            p: Params = {"ln1": jnp.zeros((cfg.d_model,), jnp.float32),
                         "ln2": jnp.zeros((cfg.d_model,), jnp.float32)}
            if kind == "attn":
                p["attn"] = init_attention(kl, cfg)
            else:
                p["rec"] = init_recurrent_block(kl, cfg)
            p["mlp"] = init_swiglu(jax.random.fold_in(kl, 1), cfg)
            layers.append(p)
        params["layers"] = layers            # heterogeneous: keep as list
        return params
    nd = 3 + cfg.n_dense_layers
    if cfg.n_dense_layers:
        params["dense_layers"] = jax.vmap(
            lambda k: _init_decoder_layer(k, cfg, dense=True))(ks[3:nd])
    params["layers"] = jax.vmap(lambda k: _init_decoder_layer(k, cfg))(
        ks[nd:])
    return params


def layer_stacks(params: Params):
    """The stacked layers' groups in the order they run: leading dense
    layers (where the config has them), then ``layers``."""
    return [params[k] for k in ("dense_layers", "layers") if k in params]


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------

def _moe_with_chunk(p: Params, x: jax.Array, cfg: ModelConfig, n_dec: int
                    ) -> Tuple[jax.Array, jax.Array]:
    """The expert layer over a step's (1, B + C) rows with a prompt
    chunk: each of the ``n_dec`` one-token rows routed as a sample of
    its own, as in a plain decode step; the C chunk rows routed together
    at a capacity of C, so that none is dropped."""
    D = x.shape[-1]
    y_dec, _ = moe_ffn(p, x[0, :n_dec, None], cfg)
    y_chunk, aux = moe_ffn(p, x[:, n_dec:], cfg,
                           capacity=x.shape[1] - n_dec)
    return jnp.concatenate([y_dec.reshape(1, n_dec, D), y_chunk], 1), aux


def _uniform_layer(cfg: ModelConfig, x, layer_p, window, positions,
                   mrope_positions=None, cache=None, cache_index=None,
                   cache_layer=None, chunk: Optional[Chunk] = None):
    """One pre-norm decoder layer; returns (x, new_cache, aux).  With
    ``cache_layer`` the cache is every layer's, stacked (see
    ``attention``), and comes back whole.  With ``chunk``, ``x`` is the
    (1, B + C) rows of a step that carries a prompt chunk (``Chunk``)."""
    aux = jnp.zeros((), jnp.float32)
    x = hint(x, "batch", None, None)
    h = rms_norm(x, layer_p["ln1"], cfg.norm_eps)
    if cfg.arch_kind == "rwkv":
        o, tstate = time_mix(layer_p["tmix"], h, cfg,
                             state=cache["tmix"] if cache else None)
        x = x + o
        h2 = rms_norm(x, layer_p["ln2"], cfg.norm_eps)
        o2, cstate = channel_mix(layer_p["cmix"], h2,
                                 state=cache["cmix"] if cache else None)
        x = x + o2
        new_cache = {"tmix": tstate, "cmix": cstate} if cache is not None \
            else None
        return x, new_cache, aux
    keys = (("c_kv", "k_pe") if cfg.kv_lora_rank else
            ("k", "v", "k_scale", "v_scale") if cfg.kv_quant else ("k", "v"))
    c_in = None if cache is None else tuple(cache[k] for k in keys)
    if cfg.kv_lora_rank:
        o, kv = mla_attention(layer_p["attn"], h, cfg, positions, cache=c_in,
                              cache_index=cache_index,
                              cache_layer=cache_layer, chunk=chunk)
    else:
        o, kv = attention(layer_p["attn"], h, cfg, positions, window=window,
                          cache=c_in, cache_index=cache_index,
                          cache_layer=cache_layer,
                          mrope_positions=mrope_positions, chunk=chunk)
    x = x + o
    h2 = rms_norm(x, layer_p["ln2"], cfg.norm_eps)
    with jax.named_scope("ffn"):
        if "moe" in layer_p and chunk is not None:
            o2, aux = _moe_with_chunk(layer_p["moe"], h2, cfg,
                                      cache_index.shape[0])
        elif "moe" in layer_p:
            o2, aux = moe_ffn(layer_p["moe"], h2, cfg)
        else:
            o2 = swiglu(layer_p["mlp"], h2, cfg)
    x = x + o2
    new_cache = None if kv is None else dict(zip(keys, kv))
    return x, new_cache, aux


def forward(params: Params, cfg: ModelConfig, tokens: jax.Array,
            patch_embeds: Optional[jax.Array] = None,
            remat: bool = True) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence forward.  Returns (logits, aux_loss)."""
    B, S_text = tokens.shape
    x = params["embed"][tokens]
    mrope_positions = None
    if cfg.arch_kind == "vlm":
        assert patch_embeds is not None
        pe = jnp.einsum("bpd,de->bpe", patch_embeds.astype(cfg.dtype),
                        params["patch_proj"])
        x = jnp.concatenate([pe, x], axis=1)
        mrope_positions = _vlm_positions(cfg, B, S_text)
    S = x.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)
    wins = static_layer_windows(cfg)

    if cfg.arch_kind == "hybrid":
        def hybrid_layer(x, layer_p):
            h = rms_norm(x, layer_p["ln1"], cfg.norm_eps)
            if "attn" in layer_p:
                o, _ = attention(layer_p["attn"], h, cfg, positions,
                                 window=(cfg.local_window or None))
            else:
                o, _ = recurrent_block(layer_p["rec"], h, cfg)
            x = x + o
            h2 = rms_norm(x, layer_p["ln2"], cfg.norm_eps)
            return x + swiglu(layer_p["mlp"], h2)

        layer_fn = jax.checkpoint(hybrid_layer) if remat else hybrid_layer
        for layer_p in params["layers"]:
            x = layer_fn(x, layer_p)
        aux_total = jnp.zeros((), jnp.float32)
    else:
        aux_total = jnp.zeros((), jnp.float32)
        if "dense_layers" in params:
            def dense_body(carry, lp):
                x, aux_acc = carry
                x, _, aux = _uniform_layer(cfg, x, lp, None, positions)
                return (x, aux_acc + aux), None

            body_fn = jax.checkpoint(dense_body) if remat else dense_body
            (x, aux_total), _ = jax.lax.scan(
                body_fn, (x, aux_total), params["dense_layers"])
        # scan over *pattern groups* so each position's attention window is
        # a static int — local layers then slice only the keys they can see
        # (chunked attention) instead of masking an S x S score matrix
        pat = (cfg.global_every
               if (cfg.local_window > 0 and cfg.global_every > 0
                   and cfg.arch_kind != "rwkv") else 1)
        L = cfg.n_layers - cfg.n_dense_layers
        wins = wins[cfg.n_dense_layers:]
        n_groups, rem = divmod(L, pat)
        pat_windows = [None if wins[j] >= BIG_WINDOW else wins[j]
                       for j in range(pat)]

        def group_body(carry, gp):
            x, aux_acc = carry
            for j in range(pat):
                lp = jax.tree.map(lambda a, j=j: a[j], gp)
                x, _, aux = _uniform_layer(cfg, x, lp, pat_windows[j],
                                           positions, mrope_positions)
                aux_acc = aux_acc + aux
            return (x, aux_acc), None

        grouped = jax.tree.map(
            lambda a: a[:n_groups * pat].reshape(n_groups, pat,
                                                 *a.shape[1:]),
            params["layers"])
        body_fn = jax.checkpoint(group_body) if remat else group_body
        (x, aux_total), _ = jax.lax.scan(body_fn, (x, aux_total), grouped)
        for l in range(n_groups * pat, L):
            lp = jax.tree.map(lambda a, l=l: a[l], params["layers"])
            win = None if wins[l] >= BIG_WINDOW else wins[l]
            layer = (lambda x_, lp_=lp, win_=win:
                     _uniform_layer(cfg, x_, lp_, win_, positions,
                                    mrope_positions)[0])
            x = jax.checkpoint(layer)(x) if remat else layer(x)

    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"])
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, params["unembed"])
    logits = hint(_mask_pad_vocab(logits, cfg), "batch", None, "model")
    if cfg.arch_kind == "vlm":
        logits = logits[:, -S_text:, :]
    return logits, aux_total / max(1, cfg.n_layers)


def _vlm_positions(cfg: ModelConfig, B: int, S_text: int) -> jax.Array:
    """M-RoPE (t,h,w) position ids: image grid then text run."""
    P = cfg.n_patches
    side = max(1, int(P ** 0.5))
    rr = jnp.arange(P, dtype=jnp.int32) // side
    cc = jnp.arange(P, dtype=jnp.int32) % side
    img = jnp.stack([jnp.zeros((P,), jnp.int32), rr, cc], axis=-1)
    t0 = jnp.int32(side)  # text starts after the image's spatial extent
    tt = t0 + jnp.arange(S_text, dtype=jnp.int32)
    txt = jnp.stack([tt, tt, tt], axis=-1)
    pos = jnp.concatenate([img, txt], axis=0)       # (P+S, 3)
    return jnp.broadcast_to(pos[None], (B, P + S_text, 3))


def loss_fn(params: Params, cfg: ModelConfig, batch: Dict[str, jax.Array]
            ) -> jax.Array:
    logits, aux = forward(params, cfg, batch["tokens"],
                          patch_embeds=batch.get("patch_embeds"))
    return cross_entropy_loss(logits, batch["labels"]) + 0.01 * aux


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=None) -> Params:
    dtype = dtype or cfg.dtype
    L, hd = cfg.n_layers, cfg.hd
    if cfg.arch_kind == "rwkv":
        H = cfg.d_model // 64
        return {
            "tmix": (jnp.zeros((L, batch, 1, cfg.d_model), dtype),
                     jnp.zeros((L, batch, H, 64, 64), jnp.float32)),
            "cmix": jnp.zeros((L, batch, 1, cfg.d_model), dtype),
        }
    if cfg.arch_kind == "hybrid":
        caches = []
        for l in range(cfg.n_layers):
            kind = cfg.block_pattern[l % len(cfg.block_pattern)]
            if kind == "attn":
                # local attention only needs a window-sized cache, but we
                # keep layout uniform and let sharding slice it
                T = min(max_len, cfg.local_window or max_len)
                caches.append({
                    "k": jnp.zeros((batch, T, cfg.n_kv_heads, hd), dtype),
                    "v": jnp.zeros((batch, T, cfg.n_kv_heads, hd), dtype)})
            else:
                caches.append({
                    "conv": jnp.zeros((batch, cfg.conv1d_width - 1,
                                       cfg.rglru_dim), dtype),
                    "h": jnp.zeros((batch, cfg.rglru_dim), jnp.float32)})
        return {"layers": caches}
    if cfg.kv_lora_rank:
        if cfg.kv_quant:
            raise ValueError("kv_quant is not supported with latent attention")
        # each position's normed latent, and its roped key part stored
        # position-minor: a 64-wide minor axis would pad to 128 lanes
        return {"c_kv": jnp.zeros((L, batch, max_len, cfg.kv_lora_rank),
                                  dtype),
                "k_pe": jnp.zeros((L, batch, cfg.qk_rope_dim, max_len),
                                  dtype)}
    # every layer stacked, each position's heads merged into one axis:
    # the form the decode step's scatter, per-layer read and attention
    # all take without a whole-cache relayout (or padding hd to a tile)
    kv = (L, batch, max_len, cfg.n_kv_heads * hd)
    if cfg.kv_quant:
        scale = (L, batch, max_len, cfg.n_kv_heads)
        return {"k": jnp.zeros(kv, jnp.int8), "v": jnp.zeros(kv, jnp.int8),
                "k_scale": jnp.zeros(scale, dtype),
                "v_scale": jnp.zeros(scale, dtype)}
    return {"k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype)}


def zero_cache_slot(cfg: ModelConfig, cache: Params, slot: int) -> Params:
    """Clear one batch slot's rows across every array of a decode cache.

    Continuous-batching engines reuse decode slots; a refilled request
    must not attend to the previous occupant's keys/values (or carry its
    recurrent state), so its slot is wiped before prefill starts.  Works
    on any layout ``init_cache`` builds: the hybrid family stacks caches
    per layer with batch leading, every other family stacks layers first.
    """
    axis = 0 if cfg.arch_kind == "hybrid" else 1

    def clear(a):
        idx = [slice(None)] * a.ndim
        idx[axis] = slot
        return a.at[tuple(idx)].set(0)

    return jax.tree.map(clear, cache)


class ChunkedTokens(NamedTuple):
    """A serve step's tokens with one slot's prompt chunk.

    ``decode`` (B, 1): each slot's token, as a plain step takes them;
    ``chunk`` (C,): the next prompt tokens of slot ``slot``, the first
    ``length`` (1 to C) real and the rest padding.  The chunk is written
    from the slot's cursor, and the slot's row of the step's logits is
    the chunk's, at its last real token."""
    decode: jax.Array
    chunk: jax.Array
    slot: jax.Array     # () int32
    length: jax.Array   # () int32


def takes_chunks(cfg: ModelConfig) -> bool:
    """Whether ``decode_step`` takes ``ChunkedTokens``: the families
    whose cache is written at positions (stacked keys and values, or
    latents).  A recurrent state (rwkv, hybrid) or the encoder-decoder's
    cache takes one token a step."""
    return cfg.arch_kind not in ("rwkv", "hybrid", "encdec")


def reads_kv_in_place(cfg: ModelConfig) -> bool:
    """Whether ``decode_step``'s one-token rows read a stacked,
    unquantized key-value cache: on a TPU the decode-attention kernel
    reads it in place, up to each slot's cursor (``layers.attention``)."""
    return takes_chunks(cfg) and not cfg.kv_lora_rank and not cfg.kv_quant


def decode_step(params: Params, cfg: ModelConfig, tokens, cache: Params,
                index: jax.Array) -> Tuple[jax.Array, Params]:
    """One decode step.  tokens: (B, 1), or ``ChunkedTokens``; index: the
    cache fill cursor — scalar int32 when all rows decode in lockstep,
    or per-slot (B,) int32 when a continuous-batching engine advances
    each slot independently (always, with a chunk).  Returns (logits
    (B, 1, V), cache)."""
    chunk = tokens if isinstance(tokens, ChunkedTokens) else None
    index = jnp.asarray(index, jnp.int32)
    if chunk is not None:
        if not takes_chunks(cfg) or index.ndim != 1:
            raise ValueError("a prompt chunk needs a positional cache and "
                             "per-slot cursors")
        # one row of B + C tokens: every projection reads its weights
        # once for the slots' tokens and the chunk together
        tokens = chunk.decode
        B, C = tokens.shape[0], chunk.chunk.shape[0]
        start = index[chunk.slot]
        x = params["embed"][jnp.concatenate([tokens[:, 0], chunk.chunk])][None]
        positions = jnp.concatenate(
            [index, start + jnp.arange(C, dtype=jnp.int32)])[None]
        rows = Chunk(chunk.slot, start)
    else:
        B = tokens.shape[0]
        x = params["embed"][tokens]
        if index.ndim == 1:
            positions = index[:, None]
        else:
            positions = jnp.broadcast_to(index, (B, 1)).astype(jnp.int32)
        rows = None
    windows = static_layer_windows(cfg)

    if cfg.arch_kind == "hybrid":
        new_layers = []
        for l, layer_p in enumerate(params["layers"]):
            c = cache["layers"][l]
            h = rms_norm(x, layer_p["ln1"], cfg.norm_eps)
            if "attn" in layer_p:
                T = c["k"].shape[1]
                slot = jnp.mod(index, T)          # ring buffer for local attn
                o, kv = attention(layer_p["attn"], h, cfg,
                                  positions, window=jnp.int32(
                                      cfg.local_window or BIG_WINDOW),
                                  cache=(c["k"], c["v"]), cache_index=slot)
                # ring-buffer positions wrap; mask handled via window
                new_layers.append({"k": kv[0], "v": kv[1]})
            else:
                o, st = recurrent_block(layer_p["rec"], h, cfg,
                                        state=(c["conv"], c["h"]))
                new_layers.append({"conv": st[0], "h": st[1]})
            x = x + o
            h2 = rms_norm(x, layer_p["ln2"], cfg.norm_eps)
            x = x + swiglu(layer_p["mlp"], h2)
        new_cache = {"layers": new_layers}
    elif cfg.arch_kind == "rwkv":
        def body(carry, scanned):
            x = carry
            layer_p, c = scanned
            x, nc, _ = _uniform_layer(cfg, x, layer_p, None, positions,
                                      cache=c)
            return x, nc

        x, ncache = jax.lax.scan(body, x, (params["layers"], cache))
        new_cache = ncache
    else:
        # the stacked cache rides in the carry: each layer scatters its
        # new keys into it and reads its own rows back, so with the cache
        # donated the step updates it in place; scanned as xs/ys, every
        # layer would be sliced out and a whole new cache stacked.  The
        # cache holds every layer of every stack; a stack's layer ids
        # start where the one before it ended
        def body(carry, scanned):
            x, c = carry
            layer_p, window, l = scanned
            x, c, _ = _uniform_layer(cfg, x, layer_p, window, positions,
                                     cache=c, cache_index=index,
                                     cache_layer=l, chunk=rows)
            return (x, c), None

        new_cache, first = cache, 0
        for stack in layer_stacks(params):
            n = jax.tree.leaves(stack)[0].shape[0]
            layer_ids = jnp.arange(first, first + n, dtype=jnp.int32)
            (x, new_cache), _ = jax.lax.scan(
                body, (x, new_cache),
                (stack, jnp.asarray(windows[first:first + n], jnp.int32),
                 layer_ids))
            first += n

    with jax.named_scope("lm_head"):
        if chunk is not None:
            # the slots' rows and the chunk's last real one
            last = jax.lax.dynamic_index_in_dim(x[0], B + chunk.length - 1)
            x = jnp.concatenate([x[0, :B], last])[:, None]
        x = rms_norm(x, params["ln_f"], cfg.norm_eps)
        if cfg.tie_embeddings:
            logits = jnp.einsum("bsd,vd->bsv", x, params["embed"])
        else:
            logits = jnp.einsum("bsd,dv->bsv", x, params["unembed"])
        logits = _mask_pad_vocab(logits, cfg)
        if chunk is not None:
            logits = logits[:B].at[chunk.slot].set(logits[B])
        return logits, new_cache


# ---------------------------------------------------------------------------
# whisper-style encoder-decoder (conv frontend stubbed per assignment)
# ---------------------------------------------------------------------------

def _init_whisper(key: jax.Array, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 2 * max(cfg.n_enc_layers, cfg.n_layers) + 4)
    kidx = iter(range(len(ks)))

    def enc_layer(k):
        k1, k2 = jax.random.split(k)
        return {"ln1": jnp.zeros((cfg.d_model,), jnp.float32),
                "attn": init_attention(k1, cfg),
                "ln2": jnp.zeros((cfg.d_model,), jnp.float32),
                "mlp": init_gelu_mlp(k2, cfg)}

    def dec_layer(k):
        k1, k2, k3 = jax.random.split(k, 3)
        return {"ln1": jnp.zeros((cfg.d_model,), jnp.float32),
                "attn": init_attention(k1, cfg),
                "ln_x": jnp.zeros((cfg.d_model,), jnp.float32),
                "xattn": init_attention(k2, cfg),
                "ln2": jnp.zeros((cfg.d_model,), jnp.float32),
                "mlp": init_gelu_mlp(k3, cfg)}

    return {
        "embed": dense_init(ks[next(kidx)], (cfg.padded_vocab, cfg.d_model),
                            cfg.dtype, scale=0.02),
        "enc_layers": _stack([enc_layer(ks[next(kidx)])
                              for _ in range(cfg.n_enc_layers)]),
        "dec_layers": _stack([dec_layer(ks[next(kidx)])
                              for _ in range(cfg.n_layers)]),
        "ln_enc": jnp.zeros((cfg.d_model,), jnp.float32),
        "ln_f": jnp.zeros((cfg.d_model,), jnp.float32),
    }


def encode_frames(params: Params, cfg: ModelConfig, frames: jax.Array,
                  remat: bool = True) -> jax.Array:
    """frames: (B, T_enc, D) precomputed embeddings (stub frontend)."""
    x = frames.astype(cfg.dtype) + sinusoidal_positions(
        frames.shape[1], cfg.d_model).astype(cfg.dtype)
    positions = jnp.arange(frames.shape[1], dtype=jnp.int32)

    def body(x, layer_p):
        h = rms_norm(x, layer_p["ln1"], cfg.norm_eps)
        o, _ = attention(layer_p["attn"], h, cfg, positions, causal=False,
                         rope=False)
        x = x + o
        h2 = rms_norm(x, layer_p["ln2"], cfg.norm_eps)
        return x + gelu_mlp(layer_p["mlp"], h2), None

    body_fn = jax.checkpoint(body) if remat else body
    x, _ = jax.lax.scan(body_fn, x, params["enc_layers"])
    return rms_norm(x, params["ln_enc"], cfg.norm_eps)


def whisper_forward(params: Params, cfg: ModelConfig, frames: jax.Array,
                    tokens: jax.Array, remat: bool = True) -> jax.Array:
    enc = encode_frames(params, cfg, frames, remat)
    B, S = tokens.shape
    x = params["embed"][tokens] + sinusoidal_positions(
        S, cfg.d_model).astype(cfg.dtype)
    positions = jnp.arange(S, dtype=jnp.int32)

    def body(x, layer_p):
        h = rms_norm(x, layer_p["ln1"], cfg.norm_eps)
        o, _ = attention(layer_p["attn"], h, cfg, positions, rope=False)
        x = x + o
        hx = rms_norm(x, layer_p["ln_x"], cfg.norm_eps)
        x = x + cross_attention(layer_p["xattn"], hx, enc, cfg)
        h2 = rms_norm(x, layer_p["ln2"], cfg.norm_eps)
        return x + gelu_mlp(layer_p["mlp"], h2), None

    body_fn = jax.checkpoint(body) if remat else body
    x, _ = jax.lax.scan(body_fn, x, params["dec_layers"])
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = jnp.einsum("bsd,vd->bsv", x, params["embed"])
    return hint(_mask_pad_vocab(logits, cfg), "batch", None, "model")


def whisper_loss_fn(params: Params, cfg: ModelConfig,
                    batch: Dict[str, jax.Array]) -> jax.Array:
    logits = whisper_forward(params, cfg, batch["frames"], batch["tokens"])
    return cross_entropy_loss(logits, batch["labels"])


def whisper_decode_step(params: Params, cfg: ModelConfig, tokens: jax.Array,
                        cache: Params, index: jax.Array
                        ) -> Tuple[jax.Array, Params]:
    """cache = {"enc": (B,T,D) encoded audio, "k"/"v": self-attn cache}."""
    B = tokens.shape[0]
    x = params["embed"][tokens] + sinusoidal_positions(
        1, cfg.d_model).astype(cfg.dtype)
    positions = jnp.broadcast_to(index, (B, 1)).astype(jnp.int32)
    enc = cache["enc"]

    def body(carry, scanned):
        x = carry
        layer_p, ck, cv = scanned
        h = rms_norm(x, layer_p["ln1"], cfg.norm_eps)
        o, kv = attention(layer_p["attn"], h, cfg, positions, rope=False,
                          cache=(ck, cv), cache_index=index)
        x = x + o
        hx = rms_norm(x, layer_p["ln_x"], cfg.norm_eps)
        x = x + cross_attention(layer_p["xattn"], hx, enc, cfg)
        h2 = rms_norm(x, layer_p["ln2"], cfg.norm_eps)
        x = x + gelu_mlp(layer_p["mlp"], h2)
        return x, (kv[0], kv[1])

    x, (nk, nv) = jax.lax.scan(body, x,
                               (params["dec_layers"], cache["k"], cache["v"]))
    new_cache = {"enc": enc, "k": nk, "v": nv}
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = jnp.einsum("bsd,vd->bsv", x, params["embed"])
    return _mask_pad_vocab(logits, cfg), new_cache
