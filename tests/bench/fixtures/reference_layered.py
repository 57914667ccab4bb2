"""A reference whose layers differ, for the harness's tests.

Layer ``index`` of the model attends within the last ``sliding_window``
keys, unless ``(index + 1) % sliding_window_pattern == 0``, which
attends to every key before it (the program's ``local_window`` and
``global_every``).  Its feed-forward block is the dense SwiGLU or the
routed experts of the MoE reference, as its stack's leaves say.  Leaves
arrive keyed by their whole path, ``<stack>/attn/wq``; the dense and MoE
references read ``layers/attn/wq``.
"""
import jax
import jax.numpy as jnp

from bench.reference import dense, moe
from bench.reference.dense import (fp8_round, matmul, prepare,  # noqa: F401
                                   rms_norm, rope)

GLOBAL = 1 << 30


def window(c: dict, index) -> jax.Array:
    """The keys layer ``index`` sees back from each query, itself
    included."""
    pattern = c.get("sliding_window_pattern", 0)
    is_global = (index + 1) % pattern == 0 if pattern else False
    return jnp.where(is_global, GLOBAL, c["sliding_window"])


def attention(p, h, c, mode, win):
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
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("nqhd,nkhd->nhqk", q, k) / jnp.sqrt(jnp.float32(hd))
    i = jnp.arange(T)[:, None]
    j = jnp.arange(T)[None, :]
    s = jnp.where((j <= i) & (i - j < win), s, -jnp.inf)
    o = jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(s, -1), v)
    return matmul(o.reshape(n, T, H * hd), p["layers/attn/wo"], mode)


def layer(p: dict, x: jax.Array, c: dict, mode: str, index) -> jax.Array:
    p = {"layers/" + k.split("/", 1)[1]: v for k, v in p.items()}
    ffn = moe.ffn if "layers/moe/router" in p else dense.ffn
    eps = c["rms_norm_eps"]
    x = x + attention(p, rms_norm(x, p["layers/ln1"], eps), c, mode,
                      window(c, index))
    return x + ffn(p, rms_norm(x, p["layers/ln2"], eps), c, mode)
