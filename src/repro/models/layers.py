"""Attention (GQA / RoPE / M-RoPE / sliding window / KV cache), latent
attention (MLA) over a latent cache, MLPs, MoE (softmax or sigmoid
routing, shared experts, a chip's share of the experts).

All layers are einsum-based so GSPMD can shard them; activations follow
(batch, seq, ...) layout.  Decode paths take a KV cache and a fill
cursor (``cache_index``, shared or per batch row) and write each row's
new keys and values with one scatter per cache array; the uniform-layer
decode step carries the whole stacked cache through its layer scan and
donates it, so the scatter updates it in place; on a TPU its one-token
rows read their layer's rows in place, up to each row's cursor, through
``kernels/decode_attention``.  A serve step may carry
a prompt ``Chunk`` behind its one-token rows: the projections run over
all the rows at once, and the chunk reads and writes its own slot.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.hints import hint, hint_any
from repro.kernels.decode_attention import block_t, decode_attention

from .common import (ModelConfig, Params, apply_mrope, apply_rope, dense_init,
                     rms_norm)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def init_attention(key: jax.Array, cfg: ModelConfig,
                   d_model: Optional[int] = None) -> Params:
    d = d_model or cfg.d_model
    hd = cfg.hd
    ks = jax.random.split(key, 4)
    p: Params = {
        "wq": dense_init(ks[0], (d, cfg.n_heads * hd), cfg.dtype),
        "wk": dense_init(ks[1], (d, cfg.n_kv_heads * hd), cfg.dtype),
        "wv": dense_init(ks[2], (d, cfg.n_kv_heads * hd), cfg.dtype),
        "wo": dense_init(ks[3], (cfg.n_heads * hd, d), cfg.dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads * hd,), cfg.dtype)
        p["bk"] = jnp.zeros((cfg.n_kv_heads * hd,), cfg.dtype)
        p["bv"] = jnp.zeros((cfg.n_kv_heads * hd,), cfg.dtype)
    return p


def _qkv(p: Params, x: jax.Array, cfg: ModelConfig
         ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    B, S, _ = x.shape
    hd = cfg.hd
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"])
    k = jnp.einsum("bsd,dh->bsh", x, p["wk"])
    v = jnp.einsum("bsd,dh->bsh", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = hint(q, "batch", None, "model")
    k = hint(k, "batch", None, "model")
    v = hint(v, "batch", None, "model")
    q = q.reshape(B, S, cfg.n_heads, hd)
    k = k.reshape(B, S, cfg.n_kv_heads, hd)
    v = v.reshape(B, S, cfg.n_kv_heads, hd)
    return q, k, v


def _sdpa(q: jax.Array, k: jax.Array, v: jax.Array, mask: jax.Array,
          cfg: ModelConfig) -> jax.Array:
    """(B,S,H,hd) x (B,T,Hkv,hd) -> (B,S,H,hd); GQA via head grouping."""
    B, S, H, hd = q.shape
    T = k.shape[1]
    G = H // k.shape[2]
    q = q.reshape(B, S, k.shape[2], G, hd)
    scores = jnp.einsum("bskgh,btkh->bkgst", q, k).astype(jnp.float32)
    # prefer head (TP) sharding; GQA archs whose kv*G doesn't divide the
    # model axis fall back to key-sequence sharding (attention SP)
    scores = hint_any(scores.reshape(B, -1, S, T),
                      [("batch", "model", None, None),
                       ("batch", None, None, "model")]).reshape(scores.shape)
    scores = scores / jnp.sqrt(hd).astype(jnp.float32)
    scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkh->bskgh", w, v)
    return out.reshape(B, S, H, hd)


def _sdpa_chunked(q: jax.Array, k: jax.Array, v: jax.Array,
                  cfg: ModelConfig, window: int, chunk: int = 512
                  ) -> jax.Array:
    """Flash-style chunked causal attention (no S x T materialization).

    The jnp counterpart of kernels/flash_attention.py: iterate query chunks
    sequentially; local-window layers slice only the (window + chunk) keys
    they can see, so an S=32k local layer touches 2k keys per chunk, never
    the full sequence — PipeOrgan's granularity argument applied to the
    attention producer/consumer pair.  window <= 0 means unbounded.
    """
    B, S, H, hd = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    while chunk > 64 and S % chunk != 0:   # e.g. VLM seq = text + patches
        chunk //= 2
    if S % chunk != 0:
        chunk = next((c for c in range(min(chunk, S), 0, -1)
                      if S % c == 0), S)
    w_eff = window if window and 0 < window < T else T
    ksz = min(T, w_eff + chunk)                      # static slice size
    nq = S // chunk

    def one(ci):
        q0 = ci * chunk
        qc = jax.lax.dynamic_slice(q, (0, q0, 0, 0), (B, chunk, H, hd))
        k0 = jnp.clip(q0 + chunk - ksz, 0, T - ksz)
        kc = jax.lax.dynamic_slice(k, (0, k0, 0, 0), (B, ksz, Hkv, hd))
        vc = jax.lax.dynamic_slice(v, (0, k0, 0, 0), (B, ksz, Hkv, hd))
        qpos = q0 + jnp.arange(chunk)[:, None]
        kpos = k0 + jnp.arange(ksz)[None, :]
        mask = (kpos <= qpos) & (qpos - kpos < w_eff)
        qg = qc.reshape(B, chunk, Hkv, G, hd)
        sc = jnp.einsum("bskgh,btkh->bkgst", qg, kc).astype(jnp.float32)
        sc = hint_any(sc.reshape(B, Hkv * G, chunk, ksz),
                      [("batch", "model", None, None),
                       ("batch", None, None, "model")]).reshape(sc.shape)
        sc = sc / jnp.sqrt(hd).astype(jnp.float32)
        sc = jnp.where(mask[None, None, None], sc, -1e30)
        w = jax.nn.softmax(sc, axis=-1).astype(v.dtype)
        oc = jnp.einsum("bkgst,btkh->bskgh", w, vc)
        return oc.reshape(B, chunk, H, hd)

    outs = jax.lax.map(one, jnp.arange(nq))          # (nq, B, chunk, H, hd)
    return jnp.moveaxis(outs, 0, 1).reshape(B, S, H, hd)


#: sequence length above which the no-cache path switches to chunked
#: attention (keeps the transient scores buffer ~chunk x window)
CHUNKED_ATTN_THRESHOLD = 8192


class Chunk(NamedTuple):
    """A prompt chunk riding behind a serve step's one-token rows.

    In a step that carries one, ``x`` is a single row of B + C tokens:
    first each of the B slots' one token, at its own cursor
    (``cache_index``, (B,)), then the C chunk tokens, which fill cache
    row ``slot`` from ``start``, that slot's cursor.  Writes past the
    cache's end are dropped."""
    slot: jax.Array     # () int32
    start: jax.Array    # () int32


class _Group(NamedTuple):
    """Tokens that read and write the cache as one block: tokens ``lo``
    to ``lo + rows * n`` of the step's flattened (batch, token) axes, as
    ``rows`` cache rows of ``n`` new tokens each, written from ``fill``
    (rows,).  ``slot``: the one cache row of a chunk; None: rows 0 to
    ``rows - 1``."""
    lo: int
    rows: int
    n: int
    slot: Optional[jax.Array]
    fill: jax.Array

    def take(self, a: jax.Array) -> jax.Array:
        """This group's part of a (batch, token, ...) array."""
        tail = a.shape[2:]
        if self.lo == 0 and self.rows * self.n == a.shape[0] * a.shape[1]:
            return a.reshape(self.rows, self.n, *tail)
        flat = a.reshape(-1, *tail)[self.lo:self.lo + self.rows * self.n]
        return flat.reshape(self.rows, self.n, *tail)

    def read(self, c: jax.Array, lead: Tuple[jax.Array, ...]) -> jax.Array:
        """This group's cache rows of one layer: (rows, ...)."""
        if self.slot is None:
            return c[lead]
        return c[(*lead, self.slot)][None]

    def index(self) -> Tuple[jax.Array, jax.Array]:
        """(cache rows (rows, 1), positions (rows, n)) of its writes."""
        pos = self.fill[:, None] + jnp.arange(self.n, dtype=jnp.int32)
        rows = (jnp.arange(self.rows, dtype=jnp.int32)[:, None]
                if self.slot is None else jnp.reshape(self.slot, (1, 1)))
        return rows, pos

    def mask(self, positions: jax.Array, T: int,
             window: Optional[jax.Array] = None) -> jax.Array:
        """(rows, n, T): causal, nothing past the tokens written, and
        within ``window`` positions where given."""
        kpos = jnp.arange(T)[None, None, :]
        qpos = self.take(positions)[:, :, None]
        mask = (kpos <= qpos) & (kpos < (self.fill[:, None, None] + self.n))
        if window is not None:
            mask = mask & (qpos - kpos < window)
        return mask


def _groups(B: int, S: int, cache_index: jax.Array,
            chunk: Optional[Chunk]) -> List[_Group]:
    """The step's token groups: every row, each from its own cursor; or,
    with a chunk, the one-token rows, then the chunk (last, so that its
    write wins where its slot's own row writes at the same cursor)."""
    fill = jnp.asarray(cache_index, jnp.int32)
    if chunk is None:
        return [_Group(0, B, S, None, jnp.broadcast_to(fill, (B,)))]
    n_dec = fill.shape[0]
    return [_Group(0, n_dec, 1, None, fill),
            _Group(n_dec, 1, B * S - n_dec, chunk.slot,
                   jnp.reshape(chunk.start, (1,)))]


def _join(outs: List[jax.Array], B: int, S: int) -> jax.Array:
    """The groups' (rows, n, ...) outputs back as (B, S, ...)."""
    if len(outs) == 1:
        return outs[0]
    flat = jnp.concatenate([o.reshape(-1, *o.shape[2:]) for o in outs])
    return flat.reshape(B, S, *flat.shape[1:])


def _cache_write(c: jax.Array, new: jax.Array, lead: Tuple[jax.Array, ...],
                 at: Tuple[jax.Array, jax.Array]) -> jax.Array:
    """One scatter of ``new`` (rows, n, ...) into ``c[*lead]`` at ``at``,
    a group's (rows, positions); trailing dims take ``c``'s form.
    Positions past the cache's end are dropped."""
    rows, pos = at
    upd = new.reshape(*pos.shape, *c.shape[len(lead) + 2:]).astype(c.dtype)
    return c.at[(*lead, rows, pos)].set(upd, unique_indices=True,
                                       mode="drop")


def _read_in_place(kernel, einsum) -> jax.Array:
    """The decode-attention kernel where the step is lowered for a TPU,
    the einsum path for any other platform."""
    return jax.lax.platform_dependent(tpu=kernel, default=einsum)


def _reads_in_place(g: _Group, c: jax.Array, lead: Tuple[jax.Array, ...],
                    cfg: ModelConfig) -> bool:
    """Whether the group's read can be the kernel's: one-token rows over
    the stacked cache, unquantized, positions in whole blocks, and no
    mesh for the kernel to be split over."""
    return (g.n == 1 and g.slot is None and bool(lead) and not cfg.kv_quant
            and _block(c) is not None
            and jax.sharding.get_abstract_mesh().size <= 1)


def _block(c: jax.Array) -> Optional[int]:
    """The kernel's block of positions over a stacked cache array."""
    return block_t(c.shape[2], c.shape[3] * c.dtype.itemsize)


def _decode_kernel(g: _Group, q: jax.Array, cache: Tuple[jax.Array, ...],
                   layer: jax.Array, positions: jax.Array,
                   window: Optional[jax.Array], interpret: bool = False
                   ) -> jax.Array:
    """One-token rows' attention over layer ``layer`` of the stacked cache
    ``(k, v)`` through the kernel, which reads only the positions the
    group's mask keeps: (rows, 1, H, hd)."""
    ck, cv = cache
    T = ck.shape[2]
    # the mask as a range: kpos <= qpos, kpos < fill + 1, qpos - kpos < window
    qpos = g.take(positions)[:, 0]
    hi = jnp.minimum(jnp.minimum(qpos, g.fill) + 1, T)
    lo = jnp.zeros_like(qpos) if window is None else qpos - window + 1
    out = decode_attention(q[:, 0], ck, cv, layer, lo, hi, block=_block(ck),
                           interpret=interpret)
    return out[:, None]


def attention(p: Params, x: jax.Array, cfg: ModelConfig,
              positions: jax.Array,
              window: Optional[jax.Array] = None,
              causal: bool = True,
              cache: Optional[Tuple[jax.Array, ...]] = None,
              cache_index: Optional[jax.Array] = None,
              cache_layer: Optional[jax.Array] = None,
              mrope_positions: Optional[jax.Array] = None,
              rope: bool = True,
              chunk: Optional[Chunk] = None,
              ) -> Tuple[jax.Array, Optional[Tuple[jax.Array, ...]]]:
    """Self-attention; returns (output, updated cache).

    window: traced scalar; attend only to keys within `window` positions
    (<=0 or None means unbounded).  cache: (k, v), or (k, v, k_scale,
    v_scale) under ``kv_quant``.  With ``cache_layer`` (a traced layer
    number) the arrays hold every layer, stacked as (L, B, T_max,
    Hkv*hd) with scales (L, B, T_max, Hkv), and this layer reads and
    writes ``[cache_layer]``; without it they hold one layer, (B, T_max,
    Hkv, hd).  cache_index: first free position — a scalar int32 when
    every batch row fills in lockstep, or a per-row (B,) int32 vector
    when rows advance independently (continuous batching: each decode
    slot carries its own cursor).  ``chunk``: a prompt chunk behind
    per-slot one-token rows (see ``Chunk``).
    """
    B, S, _ = x.shape
    if positions.ndim == 1:
        positions = jnp.broadcast_to(positions[None, :], (B, S))
    with jax.named_scope("attn.qkv"):
        q, k, v = _qkv(p, x, cfg)
        if rope:
            if mrope_positions is not None:
                q = apply_mrope(q, mrope_positions, cfg.rope_theta,
                                cfg.mrope_sections)
                k = apply_mrope(k, mrope_positions, cfg.rope_theta,
                                cfg.mrope_sections)
            else:
                q = apply_rope(q, positions, cfg.rope_theta)
                k = apply_rope(k, positions, cfg.rope_theta)

    if cache is not None:
        # each row writes its S new keys from its own cursor; a shared
        # cursor is the same write with every row's cursor equal
        groups = _groups(B, S, cache_index, chunk)
        lead = () if cache_layer is None else (cache_layer,)

        def read(g, c, width):
            """The group's rows, heads split out: (rows, T, Hkv, width)."""
            c = g.read(c, lead)
            return c.reshape(g.rows, c.shape[1], cfg.n_kv_heads, width)

        with jax.named_scope("attn.kv_cache"):
            if cfg.kv_quant:
                # int8 cache with per-vector scales: quantize the new
                # slice, dequantize on read (fused on TPU; HBM moves
                # 1B/elem not 2)
                k_s = (jnp.max(jnp.abs(k), axis=-1, keepdims=True) / 127.0
                       + 1e-8)
                v_s = (jnp.max(jnp.abs(v), axis=-1, keepdims=True) / 127.0
                       + 1e-8)
                new = (jnp.round(k / k_s).astype(jnp.int8),
                       jnp.round(v / v_s).astype(jnp.int8), k_s, v_s)
            else:
                new = (k, v)
            new_cache = tuple(cache)
            for g in groups:
                at = g.index()
                new_cache = tuple(_cache_write(c, g.take(n), lead, at)
                                  for c, n in zip(new_cache, new))
        with jax.named_scope("attn.core"):
            hd = q.shape[-1]
            if cfg.kv_quant:
                ck, cv, ks, vs = new_cache
            outs = []
            for g in groups:
                def einsum(g=g):
                    if cfg.kv_quant:
                        gk = (read(g, ck, hd).astype(x.dtype)
                              * read(g, ks, 1).astype(x.dtype))
                        gv = (read(g, cv, hd).astype(x.dtype)
                              * read(g, vs, 1).astype(x.dtype))
                    else:
                        gk, gv = (read(g, c, hd) for c in new_cache)
                    mask = g.mask(positions, gk.shape[1], window)
                    return _sdpa(g.take(q), gk, gv, mask, cfg)

                if _reads_in_place(g, new_cache[0], lead, cfg):
                    outs.append(_read_in_place(functools.partial(
                        _decode_kernel, g, g.take(q), new_cache, lead[0],
                        positions, window), einsum))
                else:
                    outs.append(einsum())
            out = _join(outs, B, S)
    else:
        new_cache = None
        T = S
        static_window = int(window) if isinstance(window, int) else (
            int(window) if window is not None
            and not hasattr(window, "aval") else None)
        use_chunked = (causal and S >= CHUNKED_ATTN_THRESHOLD
                       and (static_window is not None or window is None))
        if use_chunked:
            win = static_window if static_window is not None else 0
            out = _sdpa_chunked(q, k, v, cfg, win)
            out = jnp.einsum("bsh,ho->bso", out.reshape(B, S, -1), p["wo"])
            return out, None
        i = jnp.arange(S)[:, None]
        j = jnp.arange(S)[None, :]
        if causal:
            mask = j <= i
        else:
            mask = jnp.ones((S, S), dtype=bool)
        if window is not None:
            mask = mask & (i - j < window)
        mask = jnp.broadcast_to(mask[None], (B, S, T))
        with jax.named_scope("attn.core"):
            out = _sdpa(q, k, v, mask, cfg)
    with jax.named_scope("attn.out"):
        out = jnp.einsum("bsh,ho->bso", out.reshape(B, S, -1), p["wo"])
    return out, new_cache


def init_cross_attention(key: jax.Array, cfg: ModelConfig) -> Params:
    return init_attention(key, cfg)


def cross_attention(p: Params, x: jax.Array, enc: jax.Array,
                    cfg: ModelConfig) -> jax.Array:
    """Decoder cross-attention onto encoder output (no cache growth)."""
    B, S, _ = x.shape
    T = enc.shape[1]
    hd = cfg.hd
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"]).reshape(B, S, cfg.n_heads, hd)
    k = jnp.einsum("btd,dh->bth", enc, p["wk"]).reshape(B, T, cfg.n_kv_heads, hd)
    v = jnp.einsum("btd,dh->bth", enc, p["wv"]).reshape(B, T, cfg.n_kv_heads, hd)
    mask = jnp.ones((B, S, T), dtype=bool)
    out = _sdpa(q, k, v, mask, cfg)
    return jnp.einsum("bsh,ho->bso", out.reshape(B, S, -1), p["wo"])


# ---------------------------------------------------------------------------
# latent attention (MLA, DeepSeek-V2/V3)
# ---------------------------------------------------------------------------

def init_mla(key: jax.Array, cfg: ModelConfig) -> Params:
    """Queries from ``wq``; one latent per position from ``wkv_a`` (its
    first ``kv_lora_rank`` columns, normed by ``kv_norm``) with a rotary
    key part shared by the heads (the last ``qk_rope_dim``); ``wkv_b``
    expands the latent to each head's [k_nope | v]."""
    d, H, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    nope, rope, v = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], (d, H * (nope + rope)), cfg.dtype),
        "wkv_a": dense_init(ks[1], (d, r + rope), cfg.dtype),
        "kv_norm": jnp.zeros((r,), jnp.float32),
        "wkv_b": dense_init(ks[2], (r, H * (nope + v)), cfg.dtype),
        "wo": dense_init(ks[3], (H * v, d), cfg.dtype),
    }


def mla_attention(p: Params, x: jax.Array, cfg: ModelConfig,
                  positions: jax.Array,
                  cache: Optional[Tuple[jax.Array, jax.Array]] = None,
                  cache_index: Optional[jax.Array] = None,
                  cache_layer: Optional[jax.Array] = None,
                  chunk: Optional[Chunk] = None,
                  ) -> Tuple[jax.Array, Optional[Tuple[jax.Array, ...]]]:
    """Causal latent attention; returns (output, updated cache).

    Without a cache the latent is expanded to per-head keys and values
    over the sequence (the published form).  With one the step is
    absorbed: the cache holds each position's normed latent, (L, B,
    T_max, kv_lora_rank), and its roped key part stored position-minor,
    (L, B, qk_rope_dim, T_max), so no axis of the cache is padded to a
    tile; each head's query is taken into latent space through its
    slice of ``wkv_b`` and scores and values are read off the latents,
    which are never expanded over the cache.  ``cache_index``,
    ``cache_layer`` and ``chunk`` as in ``attention``.
    """
    B, S, _ = x.shape
    H, r = cfg.n_heads, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    if positions.ndim == 1:
        positions = jnp.broadcast_to(positions[None, :], (B, S))
    scale = jnp.float32((nope + rope) ** -0.5)
    with jax.named_scope("attn.qkv"):
        q = jnp.einsum("bsd,dh->bsh", x, p["wq"]).reshape(B, S, H, nope + rope)
        q_nope, q_pe = q[..., :nope], q[..., nope:]
        kv = jnp.einsum("bsd,dc->bsc", x, p["wkv_a"])
        c = rms_norm(kv[..., :r], p["kv_norm"], cfg.norm_eps)
        q_pe = apply_rope(q_pe, positions, cfg.rope_theta)
        k_pe = apply_rope(kv[..., None, r:], positions, cfg.rope_theta)[:, :, 0]
    w_b = p["wkv_b"].reshape(r, H, nope + vd)

    if cache is None:
        with jax.named_scope("attn.core"):
            kv_h = jnp.einsum("bsr,rhk->bshk", c, w_b)
            k = jnp.concatenate([kv_h[..., :nope], jnp.broadcast_to(
                k_pe[:, :, None], (B, S, H, rope))], axis=-1)
            qk = jnp.concatenate([q_nope, q_pe], axis=-1)
            s = jnp.einsum("bshk,bthk->bhst", qk, k).astype(jnp.float32)
            s = s * scale
            causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
            s = jnp.where(causal, s, -1e30)
            w = jax.nn.softmax(s, axis=-1).astype(x.dtype)
            o = jnp.einsum("bhst,bthv->bshv", w, kv_h[..., nope:])
        new_cache = None
    else:
        groups = _groups(B, S, cache_index, chunk)
        lead = () if cache_layer is None else (cache_layer,)
        c_cache, pe_cache = cache
        with jax.named_scope("attn.kv_cache"):
            for g in groups:
                rows, pos = at = g.index()
                c_cache = _cache_write(c_cache, g.take(c), lead, at)
                pe_cache = pe_cache.at[(*lead, rows, slice(None), pos)].set(
                    g.take(k_pe).astype(pe_cache.dtype), unique_indices=True,
                    mode="drop")
        with jax.named_scope("attn.core"):
            q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, w_b[..., :nope])
            outs = []
            for g in groups:
                c_all, pe_all = g.read(c_cache, lead), g.read(pe_cache, lead)
                s = (jnp.einsum("bshr,btr->bhst", g.take(q_lat),
                                c_all).astype(jnp.float32)
                     + jnp.einsum("bshp,bpt->bhst", g.take(q_pe),
                                  pe_all).astype(jnp.float32)) * scale
                mask = g.mask(positions, c_all.shape[1])
                s = jnp.where(mask[:, None], s, -1e30)
                w = jax.nn.softmax(s, axis=-1).astype(x.dtype)
                outs.append(jnp.einsum("bhst,btr->bshr", w, c_all))
            o = _join(outs, B, S)
        new_cache = (c_cache, pe_cache)
    with jax.named_scope("attn.out"):
        if cache is not None:
            o = jnp.einsum("bshr,rhv->bshv", o, w_b[..., nope:])
        out = jnp.einsum("bsh,ho->bso", o.reshape(B, S, H * vd), p["wo"])
    return out, new_cache


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_swiglu(key: jax.Array, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(ks[0], (cfg.d_model, cfg.d_ff), cfg.dtype),
        "w_up": dense_init(ks[1], (cfg.d_model, cfg.d_ff), cfg.dtype),
        "w_down": dense_init(ks[2], (cfg.d_ff, cfg.d_model), cfg.dtype),
    }


def swiglu(p: Params, x: jax.Array,
           cfg: Optional[ModelConfig] = None) -> jax.Array:
    if cfg is not None and cfg.use_kernels:
        # PipeOrgan fine-grained pipelining: the (t, f) intermediate tile
        # stays in VMEM across the gate/up -> down GEMM chain
        from repro.kernels.ops import mlp_block
        return mlp_block(x, p["w_gate"], p["w_up"], p["w_down"],
                         interpret=jax.default_backend() != "tpu",
                         use_pallas=True)
    g = jnp.einsum("bsd,df->bsf", x, p["w_gate"])
    u = jnp.einsum("bsd,df->bsf", x, p["w_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    h = hint(h, "batch", None, "model")
    return hint(jnp.einsum("bsf,fd->bsd", h, p["w_down"]),
                "batch", None, None)


def init_gelu_mlp(key: jax.Array, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 2)
    return {
        "w_in": dense_init(ks[0], (cfg.d_model, cfg.d_ff), cfg.dtype),
        "b_in": jnp.zeros((cfg.d_ff,), cfg.dtype),
        "w_out": dense_init(ks[1], (cfg.d_ff, cfg.d_model), cfg.dtype),
        "b_out": jnp.zeros((cfg.d_model,), cfg.dtype),
    }


def gelu_mlp(p: Params, x: jax.Array) -> jax.Array:
    h = jnp.einsum("bsd,df->bsf", x, p["w_in"]) + p["b_in"]
    h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    h = hint(h, "batch", None, "model")
    return jnp.einsum("bsf,fd->bsd", h, p["w_out"]) + p["b_out"]


# ---------------------------------------------------------------------------
# Mixture of Experts (top-k, capacity-based, EP-shardable)
# ---------------------------------------------------------------------------

def init_moe(key: jax.Array, cfg: ModelConfig) -> Params:
    """A router over all ``n_experts``, the ``experts_held`` experts of
    this chip's share, and (where the config has them) the shared
    experts as one SwiGLU and the sigmoid router's correction bias."""
    ks = jax.random.split(key, 5)
    E, D, F = cfg.experts_held, cfg.d_model, cfg.expert_ff
    p = {
        "router": dense_init(ks[0], (D, cfg.n_experts), jnp.float32),
        "w_gate": dense_init(ks[1], (E, D, F), cfg.dtype),
        "w_up": dense_init(ks[2], (E, D, F), cfg.dtype),
        "w_down": dense_init(ks[3], (E, F, D), cfg.dtype),
    }
    if cfg.router == "sigmoid":
        p["router_bias"] = jnp.zeros((cfg.n_experts,), jnp.float32)
    if cfg.n_shared_experts:
        p["shared"] = init_swiglu(ks[4], dataclasses.replace(
            cfg, d_ff=cfg.n_shared_experts * F))
    return p


def moe_capacity(cfg: ModelConfig, seq: int) -> int:
    c = int(seq * cfg.top_k * cfg.capacity_factor / cfg.n_experts) + 1
    return max(8, -(-c // 8) * 8)  # round up to a multiple of 8


def _route(p: Params, x: jax.Array, cfg: ModelConfig):
    """(chosen experts, their gates, aux loss) of each token over all
    ``n_experts``.  softmax: top-k of the probabilities, renormalised.
    sigmoid: top-k of the scores plus the correction bias, which picks
    the experts and weighs nothing; gates are the chosen scores,
    renormalised, times ``routed_scaling``."""
    B, S, _ = x.shape
    E, K = cfg.n_experts, cfg.top_k
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), p["router"])
    if cfg.router == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(scores + p["router_bias"], K)
        gate = jnp.take_along_axis(scores, idx, axis=-1)
        probs = scores / scores.sum(-1, keepdims=True)
    else:
        probs = jax.nn.softmax(logits, axis=-1)             # (B,S,E)
        gate, idx = jax.lax.top_k(probs, K)                 # (B,S,K)
    gate = gate / jnp.clip(gate.sum(-1, keepdims=True), 1e-9)
    if cfg.routed_scaling != 1.0:
        gate = gate * cfg.routed_scaling

    # aux loss (Switch-style): E * sum_e f_e * p_e
    me = probs.mean(axis=(0, 1))                            # (E,)
    ce = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(
        jnp.ones((B * S * K,), jnp.float32)) / (B * S * K)
    return idx, gate, E * jnp.sum(me * ce)


def moe_ffn(p: Params, x: jax.Array, cfg: ModelConfig,
            expert_offset: int = 0, capacity: Optional[int] = None
            ) -> Tuple[jax.Array, jax.Array]:
    """Top-k capacity-routed MoE.  Returns (output, aux load-balance loss).

    Routing is over all ``n_experts``; the layer holds ``experts_held``
    of them, the first being ``expert_offset``, and computes their part
    of the result only (with ``ep_size`` 1, every expert's).  Tokens
    routed to experts held elsewhere take no capacity here.  Shared
    experts, where the config has them, run on every token.  Routing is
    per-sample (vmapped over batch) via stable argsort -> (E, C) gather,
    so no (T, E, C) one-hot is ever materialized and the expert
    dimension shards cleanly over the model axis (EP).  ``capacity``:
    rows an expert takes from a sample, ``moe_capacity`` where not given;
    at S a token picks an expert at most once, so none is dropped.
    """
    B, S, D = x.shape
    E, K = cfg.experts_held, cfg.top_k
    C = capacity or moe_capacity(cfg, S)

    with jax.named_scope("moe.route"):
        idx, gate, aux = _route(p, x, cfg)
        if E < cfg.n_experts:
            # another share's expert: bucket E, past this share's own
            local = idx - expert_offset
            idx = jnp.where((local >= 0) & (local < E), local, E)

    def route_one(xb, idxb, gateb):
        flat_e = idxb.reshape(-1)                           # (S*K,)
        flat_t = jnp.repeat(jnp.arange(S, dtype=jnp.int32), K)
        flat_g = gateb.reshape(-1)
        order = jnp.argsort(flat_e, stable=True)
        se, st, sg = flat_e[order], flat_t[order], flat_g[order]
        start = jnp.searchsorted(se, jnp.arange(E, dtype=se.dtype),
                                 side="left")
        slot = jnp.arange(S * K, dtype=jnp.int32) - start[se].astype(jnp.int32)
        valid = (slot < C) & (se < E)
        slot = jnp.where(valid, slot, C)
        buf = jnp.minimum(se, E - 1).astype(jnp.int32) * (C + 1) + slot
        tok1 = jnp.zeros((E * (C + 1),), jnp.int32).at[buf].set(
            jnp.where(valid, st + 1, 0))
        gbuf = jnp.zeros((E * (C + 1),), jnp.float32).at[buf].set(
            jnp.where(valid, sg, 0.0))
        tok1 = tok1.reshape(E, C + 1)[:, :C]                # (E,C) token+1
        gbuf = gbuf.reshape(E, C + 1)[:, :C]
        xe = xb[jnp.maximum(tok1 - 1, 0)] * (tok1 > 0)[..., None].astype(
            xb.dtype)                                       # (E,C,D)
        xe = hint(xe, "model", None, None)                  # EP over experts
        g = jnp.einsum("ecd,edf->ecf", xe, p["w_gate"])
        u = jnp.einsum("ecd,edf->ecf", xe, p["w_up"])
        h = jax.nn.silu(g.astype(jnp.float32)).astype(xe.dtype) * u
        ye = jnp.einsum("ecf,efd->ecd", h, p["w_down"])
        ye = ye * gbuf[..., None].astype(ye.dtype)
        out = jnp.zeros((S + 1, D), xb.dtype).at[tok1.reshape(-1)].add(
            ye.reshape(-1, D))
        return out[1:]

    with jax.named_scope("moe.experts"):
        y = jax.vmap(route_one)(x, idx, gate)
    if cfg.n_shared_experts:
        with jax.named_scope("moe.shared"):
            y = y + swiglu(p["shared"], x)
    return hint(y, "batch", None, None), aux
