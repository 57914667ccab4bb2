"""Plain float32 reference of the MoE decoder (GraniteMoe family, with
the program's equations: see the config's ``departures``).

Attention as in the dense reference.  The feed-forward block routes each
token to its ``num_experts_per_tok`` experts of highest router
probability (router in float32), weights their SwiGLU outputs by those
probabilities renormalised over the chosen experts, and sums them.  Every
expert is computed for every token and the unchosen ones are weighted by
zero: no capacity, no dropped tokens.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.reference import dense
from bench.reference.dense import (fp8_round, matmul, prepare,  # noqa: F401
                                   rms_norm)


def ffn(p: dict, h: jax.Array, c: dict, mode: str) -> jax.Array:
    k = c["num_experts_per_tok"]
    probs = jax.nn.softmax(h @ p["layers/moe/router"], -1)       # (n,T,E)
    top, _ = jax.lax.top_k(probs, k)
    chosen = probs >= top[..., -1:]
    gates = jnp.where(chosen, probs, 0.0)
    gates = gates / jnp.sum(gates, -1, keepdims=True)
    a = fp8_round(h, -1) if mode == "fp8" else h
    g = jnp.einsum("ntd,edf->ntef", a, p["layers/moe/w_gate"])
    u = jnp.einsum("ntd,edf->ntef", a, p["layers/moe/w_up"])
    m = jax.nn.silu(g) * u
    if mode == "fp8":
        m = fp8_round(m, -1)
    y = jnp.einsum("ntef,efd->nted", m, p["layers/moe/w_down"])
    return jnp.einsum("nted,nte->ntd", y, gates)


layer = functools.partial(dense.layer, ffn=ffn)
