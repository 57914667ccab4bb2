"""Plain float32 reference of the dense GQA decoder (Qwen2 family).

One pre-norm layer, written from the published description and nothing
of the program: RMSNorm, q/k/v projections with bias, rotary embedding on
the two halves of each head, causal grouped-query attention scaled by
1/sqrt(head_dim), output projection, SwiGLU MLP, residual adds.  The
caller runs it layer by layer over whole sequences (no cache, no
batching of requests) under ``default_matmul_precision("highest")``.

``mode`` picks the arithmetic: ``"f32"`` is the reference; ``"fp8w"``
rounds every bfloat16 weight to float8 e4m3 (scaled per output column)
and ``"fp8"`` also rounds every other matmul input to it (activations
per row; queries, keys and values per head vector, as an fp8 KV cache
would hold them): the controls that must fail the comparison.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def fp8_round(x: jax.Array, axis: int) -> jax.Array:
    """x rounded to float8 e4m3 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def prepare(leaves: dict, mode: str) -> dict:
    """float32 copies of one layer's (or the globals') leaves; in a control
    mode the bfloat16 matrices are rounded to fp8 over their input axis."""
    out = {}
    for k, v in leaves.items():
        w = v.astype(jnp.float32)
        if mode != "f32" and v.dtype == jnp.bfloat16 and v.ndim >= 2:
            w = fp8_round(w, axis=-2)
        out[k] = w
    return out


def matmul(a: jax.Array, w: jax.Array, mode: str) -> jax.Array:
    if mode == "fp8":
        a = fp8_round(a, axis=-1)
    return a @ w


def rms_norm(x, s, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1 + s)


def rope(x, theta):
    """x: (n, T, heads, hd); positions 0..T-1 in every row."""
    n, T, _, hd = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs     # (T, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(p: dict, h: jax.Array, c: dict, mode: str) -> jax.Array:
    n, T, _ = h.shape
    H, KV, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    q = matmul(h, p["layers/attn/wq"], mode)
    k = matmul(h, p["layers/attn/wk"], mode)
    v = matmul(h, p["layers/attn/wv"], mode)
    if c["attention_bias"]:
        q = q + p["layers/attn/bq"]
        k = k + p["layers/attn/bk"]
        v = v + p["layers/attn/bv"]
    q = rope(q.reshape(n, T, H, hd), c["rope_theta"])
    k = rope(k.reshape(n, T, KV, hd), c["rope_theta"])
    v = v.reshape(n, T, KV, hd)
    if mode == "fp8":
        q, k, v = (fp8_round(a, axis=-1) for a in (q, k, v))
    k = jnp.repeat(k, H // KV, axis=2)        # head j reads kv head j // G
    v = jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("nqhd,nkhd->nhqk", q, k) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(s, -1), v)
    return matmul(o.reshape(n, T, H * hd), p["layers/attn/wo"], mode)


def swiglu(wg, wu, wd, h, mode):
    return matmul(jax.nn.silu(matmul(h, wg, mode)) * matmul(h, wu, mode),
                  wd, mode)


def ffn(p: dict, h: jax.Array, c: dict, mode: str) -> jax.Array:
    return swiglu(p["layers/mlp/w_gate"], p["layers/mlp/w_up"],
                  p["layers/mlp/w_down"], h, mode)


def layer(p: dict, x: jax.Array, c: dict, mode: str, ffn=ffn) -> jax.Array:
    eps = c["rms_norm_eps"]
    x = x + attention(p, rms_norm(x, p["layers/ln1"], eps), c, mode)
    return x + ffn(p, rms_norm(x, p["layers/ln2"], eps), c, mode)
