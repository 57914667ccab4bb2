"""Plain float32 reference of the DeepSeek-V3-style decoder (Moonlight),
in the published form.

Attention (MLA, no query LoRA): queries from ``wq``, each head's
[q_nope | q_pe]; one latent per position from ``wkv_a``, whose first
``kv_lora_rank`` values are RMS-normed and expanded by ``wkv_b`` to each
head's [k_nope | v] over the whole sequence, and whose last
``qk_rope_head_dim`` are the key's rotary part, shared by the heads;
rotary embedding on q_pe and k_pe (the two halves, see the config's
``departures``); causal softmax attention over [q_nope | q_pe] .
[k_nope | k_pe] scaled by 1/sqrt(qk_nope_head_dim + qk_rope_head_dim);
``wo``.  The first ``first_k_dense_replace`` layers end in a SwiGLU; the
others route each token over all ``n_routed_experts`` by sigmoid score,
choose its ``num_experts_per_tok`` by score plus the correction bias,
weight the chosen by their scores renormalised and times
``routed_scaling_factor``, compute only the experts of this chip's share
(``n_routed_experts / ep_size`` of them from ``offset``; every expert is
computed for every token and the unchosen weighed by zero: no capacity)
and add the shared experts.  Leaves arrive keyed by their whole path
(``dense_layers/attn/wq``); the layer reads them as ``layers/...``.
``prepare``, ``matmul``, ``fp8_round`` and ``rms_norm`` are the dense
reference's, so the fp8 controls round the same inputs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import dense
from bench.reference.dense import (fp8_round, matmul, prepare,  # noqa: F401
                                   rms_norm, rope)


def attention(p: dict, h: jax.Array, c: dict, mode: str) -> jax.Array:
    n, T, _ = h.shape
    H, r = c["num_attention_heads"], c["kv_lora_rank"]
    nope, rd, vd = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                    c["v_head_dim"])
    q = matmul(h, p["layers/attn/wq"], mode).reshape(n, T, H, nope + rd)
    kv_a = matmul(h, p["layers/attn/wkv_a"], mode)
    latent = rms_norm(kv_a[..., :r], p["layers/attn/kv_norm"],
                      c["rms_norm_eps"])
    kv = matmul(latent, p["layers/attn/wkv_b"], mode).reshape(
        n, T, H, nope + vd)
    q_pe = rope(q[..., nope:], c["rope_theta"])
    k_pe = rope(kv_a[..., None, r:], c["rope_theta"])        # (n,T,1,rd)
    q = jnp.concatenate([q[..., :nope], q_pe], -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (n, T, H, rd))], -1)
    v = kv[..., nope:]
    if mode == "fp8":
        q, k, v = (fp8_round(a, axis=-1) for a in (q, k, v))
    s = jnp.einsum("nqhd,nkhd->nhqk", q, k) / jnp.sqrt(jnp.float32(nope + rd))
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(s, -1), v)
    return matmul(o.reshape(n, T, H * vd), p["layers/attn/wo"], mode)


def gates(p: dict, h: jax.Array, c: dict) -> jax.Array:
    """(n, T, n_routed_experts) weight of every expert for every token,
    zero where it is not chosen."""
    scores = jax.nn.sigmoid(h @ p["layers/moe/router"])
    choice = scores + p["layers/moe/router_bias"]
    top, _ = jax.lax.top_k(choice, c["num_experts_per_tok"])
    g = jnp.where(choice >= top[..., -1:], scores, 0.0)
    return g / jnp.sum(g, -1, keepdims=True) * c["routed_scaling_factor"]


def ffn(p: dict, h: jax.Array, c: dict, mode: str, offset: int = 0
        ) -> jax.Array:
    """The routed part of experts ``offset`` .. ``offset + held - 1``
    (the leaves hold those), plus the shared experts."""
    held = p["layers/moe/w_gate"].shape[0]
    g = gates(p, h, c)[..., offset:offset + held]
    a = fp8_round(h, -1) if mode == "fp8" else h
    up = jnp.einsum("ntd,edf->ntef", a, p["layers/moe/w_up"])
    m = jax.nn.silu(jnp.einsum("ntd,edf->ntef", a, p["layers/moe/w_gate"]))
    m = m * up
    if mode == "fp8":
        m = fp8_round(m, -1)
    y = jnp.einsum("ntef,efd->nted", m, p["layers/moe/w_down"])
    return (jnp.einsum("nted,nte->ntd", y, g)
            + dense.swiglu(p["layers/moe/shared/w_gate"],
                           p["layers/moe/shared/w_up"],
                           p["layers/moe/shared/w_down"], h, mode))


def layer(p: dict, x: jax.Array, c: dict, mode: str) -> jax.Array:
    p = {"layers/" + k.split("/", 1)[1]: v for k, v in p.items()}
    eps = c["rms_norm_eps"]
    x = x + attention(p, rms_norm(x, p["layers/ln1"], eps), c, mode)
    f = ffn if "layers/moe/router" in p else dense.ffn
    return x + f(p, rms_norm(x, p["layers/ln2"], eps), c, mode)
